"""The launcher's edit of the grammar: answers run to the cap at every seed."""

import importlib.util
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def load_serve():
    spec = importlib.util.spec_from_file_location("bench_serve", BENCH / "serve.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_eos_is_legal_only_where_nothing_else_is(monkeypatch):
    from ai_agent_kubectl_tpu.constrain import fsm, runtime
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    monkeypatch.setattr(fsm, "compile_token_fsm", fsm.compile_token_fsm)
    monkeypatch.setattr(runtime, "compile_token_fsm", runtime.compile_token_fsm)
    tok = ByteTokenizer()
    shipped = runtime.GrammarRuntime(tok, 512, tok.eos_ids)._fsms[0]
    load_serve().answers_run_to_the_cap()
    edited = runtime.GrammarRuntime(tok, 512, tok.eos_ids)._fsms[0]
    eos_class = edited.tok_class[tok.eos_ids[0]]
    assert shipped.class_ok[:, eos_class].sum() == shipped.accept.sum() > 0
    assert (edited.class_ok[:, eos_class] == edited.forced_eos).all()
    # no state lost its way on: wherever something was legal, something still is
    assert (edited.class_ok.any(axis=1) == shipped.class_ok.any(axis=1)).all()
    other = [c for c in range(edited.n_classes) if c != eos_class]
    assert (edited.class_ok[:, other] == shipped.class_ok[:, other]).all()
