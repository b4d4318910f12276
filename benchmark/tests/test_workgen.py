import json
from collections import Counter
from pathlib import Path

import pytest

import workgen

BENCH = Path(__file__).resolve().parent.parent
TOKENIZER = BENCH.parent / "ai_agent_kubectl_tpu" / "assets" / "tokenizer-k8s.json"
ENV = {"DECODE_BATCH_SIZE": "16"}


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def words():
    return workgen.Words(str(TOKENIZER))


def open_plan(words, seed, seconds=50.0, rate=2.4):
    return workgen.build(mix("chat-steady"), {"rate_rps": rate}, ENV, seed, seconds, words)


def window(plan, seconds=50.0):
    return [r for r in plan.schedule if 0.0 <= r.due < seconds]


def gaps(reqs, seconds=50.0):
    """Every request's gap to the next one due, the last one's to the
    window's end: the n gaps the file's rule fixes."""
    due = [r.due for r in reqs] + [seconds]
    return [round(b - a, 9) for a, b in zip(due, due[1:])]


def test_two_seeds_offer_the_same_work(words):
    a, b = open_plan(words, 1), open_plan(words, 5_000_000_123)
    wa, wb = window(a), window(b)
    assert len(wa) == len(wb) == round(2.4 * 50)
    assert Counter(r.query_tokens for r in wa) == Counter(r.query_tokens for r in wb)
    assert sum(r.query_tokens for r in wa) == sum(r.query_tokens for r in wb)
    assert sorted(gaps(wa)) == pytest.approx(sorted(gaps(wb)), abs=1e-6)
    same = lambda o: {k: v for k, v in o.items() if k != "offset"}
    assert same(a.offered) == same(b.offered)
    # another starting point in the same cycle, and other words
    la, lb = [r.query_tokens for r in wa], [r.query_tokens for r in wb]
    assert la != lb
    k = (b.offered["offset"] - a.offered["offset"]) % len(la)
    assert la[k:] + la[:k] == lb
    assert wa[0].query != wb[0].query


def test_balanced_order_spreads_the_work(words):
    w = window(open_plan(words, 5))
    lens = [r.query_tokens for r in w]
    ranked = sorted(lens)
    cut = [ranked[len(ranked) * k // 4] for k in (1, 2, 3)]
    band = lambda x: sum(x >= c for c in cut)
    # away from the wrap-around, every 4 consecutive requests cover the 4 bands
    off = open_plan(words, 5).offered["offset"]
    aligned = (-off) % 4
    groups = [lens[i:i + 4] for i in range(aligned, len(lens) - 4, 4)]
    good = sum(1 for g in groups if sorted(band(x) for x in g) == [0, 1, 2, 3])
    assert good >= len(groups) - 3


def test_same_seed_gives_the_same_schedule(words):
    a, b = open_plan(words, 42), open_plan(words, 42)
    assert [(r.due, r.query, r.client) for r in a.schedule] == \
           [(r.due, r.query, r.client) for r in b.schedule]


def test_gaps_sum_to_the_window_and_lengths_keep_the_tail(words):
    w = window(open_plan(words, 3))
    assert w[0].due == 0.0
    lens = sorted(r.query_tokens for r in w)
    assert lens[0] >= 20 and lens[-1] <= 300 and lens[-1] >= 250
    assert 50 <= lens[len(lens) // 2] <= 70


def test_queries_are_distinct_and_words_are_single_tokens(words):
    from tokenizers import Tokenizer
    tok = Tokenizer.from_file(str(TOKENIZER))
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt
    w = window(open_plan(words, 9))
    assert len({r.query for r in w}) == len(w)
    overhead = len(tok.encode(render_prompt(w[0].query)).ids) - w[0].query_tokens
    for r in w[:25]:
        assert len(tok.encode(render_prompt(r.query)).ids) == r.query_tokens + overhead


def closed(name, seed, words, n):
    plan = workgen.build(mix(name), {}, ENV, seed, 50.0, words)
    return plan, [[plan.next_request(c) for c in range(len(plan.starts))]
                  for _ in range(n)]


def test_agent_sessions_same_work_per_session(words):
    pa, ra = closed("agent-sessions", 1, words, 12)
    pb, rb = closed("agent-sessions", 2, words, 12)
    assert len(pa.starts) == len(pb.starts) == 8
    # one session = 6 turns; every agent's session offers the same tokens
    for agent in range(8):
        for s in (0, 6):
            ta = [ra[s + t][agent].query_tokens for t in range(6)]
            tb = [rb[s + t][agent].query_tokens for t in range(6)]
            assert ta[-1] == tb[-1] == 2048 + 1200
            assert ta == sorted(ta) and ta[0] - 2048 in (150, 170, 190, 210, 230, 250)
    # the preamble is shared inside a cluster and differs across clusters
    pre = lambda r: " ".join(r.query.split()[:2048])
    assert pre(ra[0][0]) == pre(ra[0][2]) == pre(ra[6][0]) != pre(ra[0][1])
    # a turn extends the previous turn's query; a new session gets a new id
    assert ra[1][0].query.startswith(ra[0][0].query)
    assert ra[0][0].session == ra[5][0].session != ra[6][0].session


def test_logs_replay_fixed_sequence(words):
    pa, ra = closed("logs-replay", 1, words, 6)
    pb, rb = closed("logs-replay", 7, words, 6)
    flat = lambda rr: [r for row in rr for r in row]
    fa, fb = flat(ra), flat(rb)
    assert len(pa.starts) == 16
    spec = mix("logs-replay")
    q = spec["question_tokens"]
    # any 48 consecutive positions hold each log length exactly three times
    for f in (fa, fb):
        c = Counter(r.query_tokens - q for r in f[30:78])
        assert set(c) <= set(spec["log_tokens"])
    assert [r.tag for r in fa[:6]] == ["ask0", "ask1", "ask2"] * 2
    # position 3m asks log m first; position 3(m+5)+1 asks it again
    first, again = fa[3 * 4], fa[3 * (4 + 5) + 1]
    assert first.query.split()[:first.query_tokens - q] == again.query.split()[:again.query_tokens - q]
    assert first.query != again.query
    assert sum(r.query_tokens for r in fa[48:96]) == sum(r.query_tokens for r in fb[48:96])
