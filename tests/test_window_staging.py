"""Which staged admissions a mixed chunk carries, and what they answer
(ISSUE 39).

The prologue computes its window's valid rows, so the staged suffixes a
chunk carries are bounded by their SUM (``regime.stage_window``, one rule
for the jax and the fake scheduler): arrival order, as many as fit the
widest bucket, the rest deferred a chunk with their slots sitting it out.
Held here as a rule, in both schedulers, and end to end: on one tiny
configuration a family the ragged regime (packed windows, interpreted
kernel) answers byte for byte what the gather regime answers (one-sequence
prefills, nothing packed) at temperature 0 and 0.9, with staged suffixes
beside riders, a deferred one, dead slots and a request that ends in the
prologue.
"""

import asyncio

import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.engine.regime import stage_window

# ---------------------------------------------------------------- the rule

BUCKETS = (64, 128, 256, 512)


@pytest.mark.parametrize("lengths,taken,width", [
    ([], 0, 0),
    ([1], 1, 64),
    ([64], 1, 64),
    ([65], 1, 128),
    ([100, 100], 2, 256),
    ([100, 100, 100, 100, 100], 5, 512),
    ([100, 100, 100, 100, 100, 100], 5, 512),     # the sixth waits
    ([512, 1], 1, 512),                           # nothing rides beside it
    ([300, 300, 10], 1, 512),       # order kept: the 10 does not overtake
    ([10, 300, 300], 2, 512),
    ([512, 512, 512], 1, 512),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else str(v))
def test_staging_rule(lengths, taken, width):
    assert stage_window(lengths, BUCKETS) == (taken, width)


def test_the_line_drains_in_order_and_nothing_starves():
    waiting, carried = [300, 300, 10, 512, 40, 40, 500], []
    while waiting:
        taken, width = stage_window(waiting, BUCKETS)
        assert taken >= 1 and sum(waiting[:taken]) <= width <= BUCKETS[-1]
        carried.append(waiting[:taken])
        waiting = waiting[taken:]
    assert carried == [[300], [300, 10], [512], [40, 40], [500]]


# ------------------------------------------------------- both schedulers


def _jax_engine(model="toy-8m", **kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    defaults = dict(dtype="float32", max_seq_len=256, prefill_buckets=(32, 64),
                    prefix_cache=False, batch_size=4, chunk_len=4,
                    kv_pool_page=16)
    if get_config(model).keeps_state:
        defaults.update(state_snapshots=8, kv_pool_blocks=96,
                        radix_lru_blocks=64)
    defaults.update(kw)
    return BatchedJaxEngine(get_config(model), tokenizer=ByteTokenizer(),
                            **defaults)


def _engine(kind):
    if kind == "fake":
        return FakeChunkedEngine(batch_size=4, chunk_len=4, force_ragged=True,
                                 max_seq_len=2048)
    return _jax_engine(force_ragged=True)


#: prompts whose staged suffixes cannot share a window: the jax engine's
#: widest bucket is 64 byte tokens, the fake's 1,024 of its own
ASKS = {"jax": ["describe the deployment called web-%d in prod" % i for i in range(3)],
        "fake": [("node-%d " % i) * 600 for i in range(3)]}


@pytest.mark.parametrize("kind", ["fake", "jax"])
async def test_suffixes_that_do_not_fit_wait_a_chunk_in_order(kind):
    eng = _engine(kind)
    await eng.start()
    try:
        before = eng.stats()["ragged"]["window"]
        outs = await asyncio.gather(*[
            eng.generate(p, max_tokens=10, temperature=0.0, seed=i)
            for i, p in enumerate(ASKS[kind])])
        assert all(o.text for o in outs)
        window = eng.stats()["ragged"]["window"]
        grew = {k: window[k] - before[k] for k in window}
        # three asks, each alone in its chunk's window: two waited once
        # or twice behind the head of the line
        assert grew["windows"] == 3 and grew["deferred"] == 3, grew
        assert 0 < grew["rows_valid"] <= grew["rows_computed"], grew
        carried = [e for e in eng._chunk_log if e.get("event") == "dispatch"
                   and e.get("admissions")]
        assert [e["admissions"] for e in carried[-3:]] == [1, 1, 1]
        chunks = [e["chunk"] for e in carried[-3:]]
        assert chunks == sorted(chunks) and len(set(chunks)) == 3
        assert not eng._pending_adm
    finally:
        await eng.stop()


async def test_fake_deferral_leaves_the_transcripts_alone():
    """A deferred slot sits its chunk out: what it then answers is what it
    answers with the line to itself (and what the gather mirror answers)."""
    crowded = _engine("fake")
    alone = FakeChunkedEngine(batch_size=4, chunk_len=4, max_seq_len=2048)
    await crowded.start()
    await alone.start()
    try:
        got = await asyncio.gather(*[
            crowded.generate(p, max_tokens=12, temperature=0.9, seed=i)
            for i, p in enumerate(ASKS["fake"])])
        want = [await alone.generate(p, max_tokens=12, temperature=0.9, seed=i)
                for i, p in enumerate(ASKS["fake"])]
        assert [o.text for o in got] == [o.text for o in want]
        assert crowded.stats()["ragged"]["window"]["deferred"] >= 1
        assert alone.stats()["ragged"] is None
    finally:
        await crowded.stop()
        await alone.stop()


# ----------------------------------------- packed windows, end to end

LONG = "pod web-1 crashed with OOMKilled at 12:03; why? "        # 48 byte tokens


async def _traffic(eng):
    """Three asks at once (their suffixes do not fit one window; one ends in
    its prologue, at a budget of one token), a fourth while they decode (it
    stages beside riders), then one alone (a window with dead slots)."""
    first = asyncio.gather(
        eng.generate(LONG + "a", max_tokens=9, temperature=0.0, seed=3),
        eng.generate(LONG + "bb", max_tokens=1, temperature=0.9, seed=4),
        eng.generate("list pods", max_tokens=9, temperature=0.9, seed=5))
    await asyncio.sleep(0.05)
    late = eng.generate("rollout status web", max_tokens=9, temperature=0.9,
                        seed=6)
    outs = list(await first) + [await late]
    outs.append(await eng.generate("get nodes", max_tokens=6, temperature=0.0,
                                   seed=7))
    return [o.text for o in outs]


@pytest.mark.parametrize("model", ["toy-8m", "toy-moe", "toy-sparse-moe",
                                   "toy-hybrid-moe", "toy-mla-moe"])
async def test_packed_windows_answer_what_one_sequence_prefills_answer(model):
    packed = _jax_engine(model, force_ragged=True)
    plain = _jax_engine(model)
    await packed.start()
    plain.tokenizer = packed.tokenizer
    await plain.start()
    try:
        assert packed._use_ragged and not plain._use_ragged
        got, want = await _traffic(packed), await _traffic(plain)
        assert got == want
        window = packed.stats()["ragged"]["window"]
        assert window["windows"] >= 3
        assert window["rows_computed"] == sum(
            e["adm_w"] + packed.batch_size for e in packed._chunk_log
            if e.get("event") == "dispatch" and e.get("admissions"))
        assert window["rows_valid"] <= window["rows_computed"]
        assert plain.stats()["ragged"] is None
    finally:
        await asyncio.gather(packed.stop(), plain.stop())
