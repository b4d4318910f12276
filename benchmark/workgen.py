"""The one traffic generator. A mix is a data file under ``traffic/``; this
module turns its parameters, the cell's rate and ``--seed`` into requests.

The rule every mix keeps: the file (with the cell's rate and the window's
length) fixes HOW MUCH work is offered — the multiset of prompt lengths, the
number of requests, the multiset of gaps. ``--seed`` chooses the words and the
ORDER, nothing else. Two seeds offer identical request counts, prompt-token
totals and gap multisets (benchmark/tests/test_workgen.py).
"""

from __future__ import annotations

import json
import math
import random
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Callable, Dict, List, Optional


@dataclass
class Request:
    due: float                     # seconds from the window's start (ramp: < 0)
    query: str
    query_tokens: int              # words = tokens of the serving tokenizer
    client: str                    # X-Forwarded-For
    session: Optional[str] = None  # X-Session-ID
    tag: str = ""                  # what the mix calls this request


class Words:
    """Words that are each ONE token of the serving tokenizer when a space
    precedes them, so a query of n words is n tokens. Built from the
    tokenizer file the server loads; with no file (the rehearsal's byte
    tokenizer) a fixed list, and lengths are then only proportional."""

    FALLBACK = ("pods nodes logs error warn restart crash image pull mount "
                "probe ready node drain taint label scale apply patch rollout").split()

    def __init__(self, tokenizer_path: Optional[str]):
        if tokenizer_path is None:
            self.words = list(self.FALLBACK)
            return
        vocab = json.load(open(tokenizer_path))["model"]["vocab"]
        cand = sorted(w[1:] for w in vocab
                      if w.startswith("Ġ") and w[1:].isascii()
                      and w[1:].isalpha() and w[1:].islower() and len(w) >= 4)
        try:
            from tokenizers import Tokenizer
            tok = Tokenizer.from_file(tokenizer_path)
            cand = [w for w in cand
                    if len(tok.encode(" " + w, add_special_tokens=False).ids) == 1]
        except ImportError:
            pass
        if len(cand) < 100:
            raise ValueError(f"only {len(cand)} single-token words in {tokenizer_path}")
        self.words = cand

    def text(self, rng: random.Random, n: int) -> str:
        return " ".join(rng.choices(self.words, k=n))

    def new_text(self, rng: random.Random, n: int, seen: set) -> str:
        """n words not in ``seen`` (a repeated query would be answered from
        the response cache)."""
        for _ in range(1000):
            t = self.text(rng, n)
            if t not in seen:
                seen.add(t)
                return t
        raise ValueError(f"no unused text of {n} words left")


def _rng(seed: int, *stream) -> random.Random:
    return random.Random(":".join(str(s) for s in (seed,) + stream))


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """n lengths: the (i + 0.5)/n quantiles of the file's lognormal, rounded
    and clipped — a fixed multiset for a given n."""
    nd = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def quantile_gaps(n: int, total: float) -> List[float]:
    """n gaps: the (i + 0.5)/n quantiles of the unit exponential, scaled to
    sum to ``total``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total / sum(raw)
    return [g * scale for g in raw]


def balanced_order(values: List, strata: int, rng: random.Random) -> List:
    """The values in an order in which every run of ``strata`` consecutive
    items holds one item from each rank-stratum of the multiset (its smallest
    1/strata, the next, ... its largest): the work is spread evenly along the
    sequence and no stretch of it is all short or all long."""
    ranked = sorted(values)
    n = len(ranked)
    bands = [ranked[n * k // strata: n * (k + 1) // strata] for k in range(strata)]
    for band in bands:
        rng.shuffle(band)
    out = []
    while any(bands):
        group = [band.pop() for band in bands if band]
        rng.shuffle(group)
        out.extend(group)
    return out


def rotated(seq: List, offset: int, start: int, count: int) -> List:
    """``count`` items of the cyclic sequence from place offset + start."""
    n = len(seq)
    return [seq[(offset + start + i) % n] for i in range(count)]


def _client(i: int) -> str:
    return f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"


@dataclass
class Plan:
    loop: str
    ramp_s: float
    tail_max_s: float
    #: open loop: every request with its due time (ramp < 0 <= measured <
    #: seconds <= tail), the tail long enough for tail_max_s.
    schedule: List[Request] = field(default_factory=list)
    #: closed loop: client index -> first send time; next_request(client)
    #: hands out the mix's fixed sequence.
    starts: List[float] = field(default_factory=list)
    next_request: Optional[Callable[[int], Request]] = None
    offered: Dict[str, object] = field(default_factory=dict)


def _open_plan(mix: dict, rate: float, seed: int, seconds: float,
               words: Words, scale: float) -> Plan:
    """One fixed cyclic sequence of (length, gap) pairs — the file's two
    multisets, each in a balanced order drawn with the file's own
    ``sequence_seed`` — and ``--seed`` picks where in the cycle the window
    starts. The ramp is the stretch of the cycle before it and the tail the
    stretch after it, so every seed offers the same requests with the same
    neighbours, from another starting point and with other words."""
    spec = dict(mix["query_tokens"])
    if scale != 1.0:
        spec.update(median=max(2, spec["median"] * scale), min=2,
                    max=max(4, int(spec["max"] * scale)))
    plan = Plan("open", mix["ramp_s"], mix["tail_max_s"])
    n = max(1, round(rate * seconds))
    fixed = _rng(mix["sequence_seed"], mix["name"], "sequence")
    lens = balanced_order(quantile_lengths(spec, n), mix["balance_strata"], fixed)
    gaps = balanced_order(quantile_gaps(n, seconds), mix["balance_strata"], fixed)
    offset = _rng(seed, mix["name"], "offset").randrange(n)
    text = _rng(seed, mix["name"], "words")
    n_clients = mix["clients"]
    serial = 0
    seen: set = set()

    def part(name: str, t0: float, start: int, count: int, length: float) -> List[Request]:
        nonlocal serial
        ls, gs = rotated(lens, offset, start, count), rotated(gaps, offset, start, count)
        fit = length / sum(gs)          # 1.0 for the window itself
        reqs, t = [], t0
        for ln, gap in zip(ls, gs):
            reqs.append(Request(t, words.new_text(text, ln, seen), ln,
                                _client(serial % n_clients), tag=name))
            serial += 1
            t += gap * fit
        return reqs

    n_ramp = max(1, round(rate * mix["ramp_s"]))
    n_tail = max(1, round(rate * mix["tail_max_s"]))
    ramp = part("ramp", -mix["ramp_s"], -n_ramp, n_ramp, mix["ramp_s"])
    measured = part("window", 0.0, 0, n, seconds)
    tail = part("tail", seconds, n, n_tail, mix["tail_max_s"])
    plan.schedule = ramp + measured + tail
    plan.offered = {
        "requests": len(measured),
        "query_tokens_total": sum(r.query_tokens for r in measured),
        "gap_multiset_digest": round(sum(g * g for g in gaps), 9),
        "ramp_requests": len(ramp),
        "offset": offset,
    }
    return plan


def _agent_sessions(mix: dict, seed: int, words: Words, scale: float,
                    n_agents: int) -> Callable[[int], Request]:
    n_clusters = mix["clusters"]
    pre_n = max(4, int(mix["preamble_tokens"] * scale))
    added = [max(2, int(a * scale)) for a in mix["turn_added_tokens"]]
    preambles = [words.text(_rng(seed, mix["name"], "preamble", c), pre_n)
                 for c in range(n_clusters)]
    state = [{"session": -1, "turn": len(added), "tools": [], "lens": []}
             for _ in range(n_agents)]

    def nxt(agent: int) -> Request:
        st = state[agent]
        if st["turn"] >= len(added):
            st["session"] += 1
            st["turn"] = 0
            lens = list(added)
            _rng(seed, mix["name"], "order", agent, st["session"]).shuffle(lens)
            text = _rng(seed, mix["name"], "tools", agent, st["session"])
            st["lens"] = lens
            st["tools"] = [words.text(text, n) for n in lens]
        st["turn"] += 1
        t = st["turn"]
        cluster = agent % n_clusters
        query = " ".join([preambles[cluster]] + st["tools"][:t])
        return Request(0.0, query, pre_n + sum(st["lens"][:t]), _client(agent),
                       session=f"s{seed % 100000}-a{agent}-n{st['session']}",
                       tag=f"turn{t}")

    return nxt


def _logs_replay(mix: dict, seed: int, words: Words, scale: float,
                 n_clients: int) -> Callable[[int], Request]:
    lens = balanced_order([max(4, int(n * scale)) for n in mix["log_tokens"]],
                          mix["balance_strata"],
                          _rng(mix["sequence_seed"], mix["name"], "sequence"))
    lens = rotated(lens, _rng(seed, mix["name"], "offset").randrange(len(lens)),
                   0, len(lens))
    asks, dist = mix["asks_per_log"], mix["ask_distance"]
    q_n = max(2, int(mix["question_tokens"] * scale))
    pos = {"p": 0}

    def nxt(client: int) -> Request:
        p = pos["p"]
        pos["p"] += 1
        m, k = divmod(p, asks)
        log = m - k * dist
        n = lens[log % len(lens)]
        text = words.text(_rng(seed, mix["name"], "log", log), n)
        question = words.text(_rng(seed, mix["name"], "q", log, k), q_n)
        return Request(0.0, text + " " + question, n + q_n, _client(client),
                       tag=f"ask{k}")

    return nxt


#: closed-loop patterns by the name a mix's file gives under "pattern".
CLOSED_PATTERNS = {"sessions": _agent_sessions, "replay": _logs_replay}


def build(mix: dict, cell: dict, server_env: dict, seed: int, seconds: float,
          words: Words, scale: float = 1.0) -> Plan:
    """The plan for one run. ``scale`` < 1 is the rehearsal's shrink."""
    if mix["pattern"] == "open-quantiles":
        return _open_plan(mix, float(cell["rate_rps"]), seed, seconds, words, scale)
    n = mix["clients"]
    if isinstance(n, dict):
        n = int(server_env[n["from_server_env"]])
    plan = Plan("closed", mix["ramp_s"], mix["tail_max_s"])
    plan.next_request = CLOSED_PATTERNS[mix["pattern"]](mix, seed, words, scale, n)
    stagger = mix.get("stagger_s", 0.0)
    plan.starts = [-mix["ramp_s"] + stagger * i / n for i in range(n)]
    plan.offered = {"clients": n}
    return plan
