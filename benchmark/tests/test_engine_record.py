import pytest

import run


def detail(first_event):
    return {"duration_ms": 7000.0, "events": [
        {"offset_ms": 1.0, "message": "engine: submitted to batch scheduler (queue depth 0, tenant 'x')"},
        {"offset_ms": 101.0, "message": "engine: admitted to slot 3 (120 prompt tokens, prefix_hit=True)"},
        {"offset_ms": 2101.0, "message": first_event},
        {"offset_ms": 2701.0, "message": "engine: chunk consumed (+16 tok, n_alive=9)"},
        {"offset_ms": 6901.0, "message": "engine: finished (length, 128 tokens)"}]}


@pytest.mark.parametrize("first_event,first_tokens", [
    ("engine: first token", 1),                             # eager admission
    ("engine: chunk consumed (+16 tok, n_alive=9)", 16),    # ragged admission
])
def test_phases_from_the_requests_trace_events(first_event, first_tokens):
    rec = run.engine_record(detail(first_event))
    assert rec["completion_tokens"] == 128 and rec["finish"] == "length"
    assert rec["queue_ms"] == 100.0 and rec["prefill_ms"] == 2000.0
    assert rec["decode_ms"] == 4800.0 and rec["handler_ms"] == 100.0
    assert rec["decode_ms_per_tok"] == pytest.approx(4800.0 / (128 - first_tokens))


def test_an_unfinished_request_has_no_phases():
    d = detail("engine: first token")
    d["events"] = d["events"][:2]
    assert run.engine_record(d) == {"completion_tokens": None, "finish": None}
