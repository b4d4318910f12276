"""TP=8 throughput projection from measured single-chip numbers.

An earlier v5e-8 claim that the Megatron shard "lands well past the
2k/chip clause" came with no arithmetic shown, and a reviewer's own
arithmetic disagreed. This tool IS the arithmetic: a per-chip step model
priced from the decode-step attribution table (or the defaults of an
earlier chip run, not re-measured), with every assumption a flag,
emitting a markdown table in place of the adjective. Its output is a
projection, never a measurement (ROADMAP D7).

Model (per decode step, Megatron TP over ``--tp`` chips):

    step_tp(B) = weights_ms/tp                      # weight stream shards
               + attn_ms · (B/bs0) / tp             # KV heads shard
               + residual(B) · ((1−f) + f/tp)       # f = TP-shardable frac
               + layers · 2 · allreduce(B·dim·bytes)

    residual(B) = residual0 · ((1−g) + g·B/bs0)     # g = per-slot frac
    residual0   = step_ms − weights_ms − attn_ms    # the attributed rest
    allreduce   = 2(n−1)/n · bytes / ici_bw + 2(n−1) · latency   (ring)

    tok/s/chip  = B / step_tp(B) / tp

``f`` (how much of the non-weight residual TP-shards) and ``g`` (how much
of it scales with batch) are exactly what the per-category attribution
table decides — sampling/LM-head shard with the vocab split, KV writes
shard with the heads, dispatch gaps shard not at all. Until the chip run
pins them, the sweep brackets the landing. Batch headroom comes from the
8×-freed weight HBM: per chip, weights/tp + B·kv_per_slot/tp must fit.

    python tools/tp_projection.py                       # r5 defaults
    python tools/tp_projection.py --attribution attribution_7b.json
"""

from __future__ import annotations

import argparse
import json
import sys


def allreduce_ms(n: int, nbytes: float, ici_gbps: float,
                 latency_us: float) -> float:
    """Ring all-reduce cost for one [B, dim] activation over n chips."""
    return (2.0 * (n - 1) / n * nbytes / (ici_gbps * 1e9) * 1e3
            + 2.0 * (n - 1) * latency_us * 1e-3)


def kv_mb_effective(a) -> float:
    """KV HBM per admitted slot. Dense: every slot owns a full
    S_alloc-deep region (kv_mb_per_slot). Pool (ISSUE 10): a slot holds
    only the pages its live span needs — avg_tokens of S_alloc — and the
    shared radix prefix (system prompt + reused histories) is counted
    ONCE fleet-wide, not per slot, so the per-slot marginal cost is the
    UNSHARED span only."""
    if not a.kv_pool:
        return a.kv_mb_per_slot
    unshared = max(1, a.avg_tokens - a.shared_prefix_tokens)
    return a.kv_mb_per_slot * unshared / a.s_alloc


def project(a) -> dict:
    residual0 = a.step_ms - a.weights_ms - a.attn_ms
    if residual0 < 0:
        raise SystemExit("step_ms must exceed weights_ms + attn_ms")
    hbm_free = (a.hbm_gb - a.reserve_gb - a.weights_gb / a.tp)
    kv_mb = kv_mb_effective(a)
    prefix_mb = (a.kv_mb_per_slot * a.shared_prefix_tokens / a.s_alloc
                 if a.kv_pool else 0.0)
    bs_max = int((hbm_free * 1e3 * a.tp - prefix_mb) / kv_mb)
    rows = []
    for f in a.f_list:
        for bs in a.batch_list:
            scale = bs / a.bs
            attn = a.attn_ms * scale / a.tp
            residual = residual0 * ((1 - a.g) + a.g * scale)
            residual_tp = residual * ((1 - f) + f / a.tp)
            ar = a.layers * 2 * allreduce_ms(
                a.tp, bs * a.dim * a.dtype_bytes, a.ici_gbps, a.ici_latency_us)
            step = a.weights_ms / a.tp + attn + residual_tp + ar
            rows.append({
                "f": f, "bs": bs, "step_ms": round(step, 2),
                "allreduce_ms": round(ar, 2),
                "tok_s_chip": round(bs / step * 1e3 / a.tp, 0),
                "fits_hbm": bs <= bs_max,
            })
    return {"residual0_ms": round(residual0, 2), "bs_max_hbm": bs_max,
            "kv_mb_per_slot_effective": round(kv_mb, 2), "rows": rows}


def render(a, out: dict) -> str:
    lines = [
        f"TP={a.tp} projection from: step {a.step_ms} ms @ bs={a.bs} "
        f"(weights {a.weights_ms} ms, attention {a.attn_ms} ms, residual "
        f"{out['residual0_ms']} ms), {a.layers}×2 all-reduces of "
        f"[bs, {a.dim}] bf16 at {a.ici_gbps} GB/s + {a.ici_latency_us} µs "
        f"ICI; g={a.g} of the residual scales with batch; "
        + (f"block-paged KV (ISSUE 10): {out['kv_mb_per_slot_effective']}"
           f" MB marginal KV/slot (avg {a.avg_tokens} live of "
           f"{a.s_alloc} rows, {a.shared_prefix_tokens} radix-shared), "
           if a.kv_pool else
           f"dense KV: {a.kv_mb_per_slot} MB/slot (every slot owns "
           f"S_alloc={a.s_alloc} rows), ")
        + f"batch ceiling ≈ {out['bs_max_hbm']} slots "
        f"({a.hbm_gb}−{a.reserve_gb} GB HBM − weights/{a.tp}).",
        "",
        "| residual TP-frac f | bs | step ms | all-reduce ms | tok/s/chip |",
        "|---|---|---|---|---|",
    ]
    for r in out["rows"]:
        note = "" if r["fits_hbm"] else " (exceeds KV pool)"
        lines.append(
            f"| {r['f']:.1f} | {r['bs']} | {r['step_ms']} "
            f"| {r['allreduce_ms']} | **{r['tok_s_chip']:.0f}**{note} |")
    return "\n".join(lines)


def implied_f(a, step_tp_ms: float, bs: int, ar_ms: float) -> float:
    """Solve the model's residual TP-fraction f back out of a MEASURED
    sharded step: step_tp = weights/tp + attn·scale/tp + residual·((1−f)
    + f/tp) + ar  ⇒  f = (1 − residual_tp/residual) · tp/(tp−1).
    Clamped to [0, 1] — measurement noise can push the division past
    either end. tp=1 is degenerate (nothing shards): f is reported 0."""
    if a.tp <= 1:
        return 0.0
    scale = bs / a.bs
    residual = (a.step_ms - a.weights_ms - a.attn_ms) \
        * ((1 - a.g) + a.g * scale)
    residual_tp = step_tp_ms - a.weights_ms / a.tp \
        - a.attn_ms * scale / a.tp - ar_ms
    if residual <= 0:
        return 0.0
    return max(0.0, min(1.0, (1.0 - residual_tp / residual)
                        * a.tp / (a.tp - 1)))


def render_measured(a, rungs: list) -> str:
    """The measured-step section (ISSUE 14): once the sharded engine
    exists, the projection re-prices from ITS step — tok/s/chip is
    arithmetic on the measurement, and the model only back-solves the
    implied f so projection and implementation converge on one number.
    ``rungs`` = [{bs, step_ms, allreduce_ms?}, ...] — the bench
    ``--phase tp7b`` sweep (driver artifact ``gemma_7b.tp_sweep``)."""
    lines = [
        "",
        f"Measured TP={a.tp} step (re-priced from the sharded engine, "
        f"not the dense-step-derived model):",
        "",
        "| bs | measured step ms | all-reduce ms | implied f "
        "| tok/s/chip |",
        "|---|---|---|---|---|",
    ]
    for r in rungs:
        bs = int(r["bs"])
        step = float(r["step_ms"])
        ar = float(r.get("allreduce_ms") or 0.0)
        f = implied_f(a, step, bs, ar)
        lines.append(
            f"| {bs} | {step:.2f} | {ar:.2f} | {f:.2f} "
            f"| **{bs / step * 1e3 / a.tp:.0f}** |")
    return "\n".join(lines)


def _descend(node: dict, *keys: str) -> dict:
    """Walk driver-wrapper / orchestrator nesting levels that may or
    may not be present (BENCH_r*.json wraps the orchestrator dict in
    ``parsed``; phases nest under ``extra.gemma_7b``)."""
    for key in keys:
        if isinstance(node, dict) and key in node:
            node = node[key]
    return node


def extract_acceptance(bench: dict):
    """Pull the measured spec acceptance out of a bench artifact:
    prefer a ``tp_spec_sweep`` rung (acceptance measured UNDER the
    mesh, and carrying the measured spec step), else the plain
    ``spec_sweep``'s highest-k rung. Returns None when the artifact
    carries neither — the composed table then refuses to print rather
    than compose with an invented ratio."""
    node = _descend(bench, "parsed", "extra", "gemma_7b")
    if not isinstance(node, dict):
        return None
    best = None
    for key, r in (node.get("tp_spec_sweep") or {}).items():
        if (isinstance(r, dict)
                and r.get("acceptance_ratio") is not None):
            best = {"acceptance": float(r["acceptance_ratio"]),
                    "k": int(r.get("spec_k", 4)),
                    "source": f"tp_spec_sweep.{key}",
                    "spec_step_ms": r.get("spec_step_ms"),
                    "bs": r.get("bs")}
    if best is not None:
        return best
    for key, r in sorted((node.get("spec_sweep") or {}).items()):
        if (isinstance(r, dict) and key.startswith("k")
                and r.get("acceptance_ratio") is not None):
            try:
                k = int(key[1:].split("_")[0])
            except ValueError:
                continue
            if best is None or k >= best["k"]:
                best = {"acceptance": float(r["acceptance_ratio"]),
                        "k": k, "source": f"spec_sweep.{key}",
                        "spec_step_ms": None, "bs": None}
    return best


def render_acceptance(a, acc: dict, rungs: list, out: dict) -> str:
    """The Spec×TP composed section (ISSUE 18): the measured TP step
    price x the measured acceptance ratio, derived in one place so
    the claim is arithmetic instead of an adjective.

    Per verify window the mesh pays one (k+1)-wide target step (the
    memory-bound weight stream is read once, same as a decode step)
    plus k+1 draft single-token steps at ``--draft-step-ratio`` r of
    the target's, and buys 1 + a·k transcript tokens:

        window_ms   = step_tp_ms · (1 + r·(k+1))
        tok/s/chip  = bs / window_ms · (1 + a·k) · 1e3 / tp

    Rows come from the measured tp_sweep rungs when present, else the
    f=1.0 projection rows; a rung that carried its own MEASURED
    spec_step_ms (bench --phase tp_spec7b) is quoted directly."""
    ar, k, r = acc["acceptance"], acc["k"], a.draft_step_ratio
    mult = (1.0 + ar * k) / (1.0 + r * (k + 1))
    lines = [
        "",
        f"Spec×TP composed (measured acceptance a={ar:.2f} at k={k} "
        f"from {acc['source']}; draft/target step ratio r={r}): "
        f"1 + a·k = {1 + ar * k:.2f} tokens bought per verify window "
        f"at {1 + r * (k + 1):.2f}× the step price — multiplier "
        f"×{mult:.2f} on the TP rung:",
        "",
        "| bs | TP step ms | window ms | tok/window | tok/s/chip "
        "(composed) |",
        "|---|---|---|---|---|",
    ]
    if rungs:
        rows = [(int(rg["bs"]), float(rg["step_ms"])) for rg in rungs]
    else:
        rows = [(rr["bs"], rr["step_ms"]) for rr in out["rows"]
                if rr["f"] == 1.0]
    for bs, step in rows:
        window = step * (1.0 + r * (k + 1))
        lines.append(
            f"| {bs} | {step:.2f} | {window:.2f} "
            f"| {1 + ar * k:.2f} "
            f"| **{bs / window * 1e3 / a.tp * (1 + ar * k):.0f}** |")
    if acc.get("spec_step_ms") and acc.get("bs"):
        sm, bs = float(acc["spec_step_ms"]), int(acc["bs"])
        lines.append(
            f"\nMeasured spec window (bench --phase tp_spec7b, "
            f"bs={bs}): {sm:.2f} ms → "
            f"**{bs / sm * 1e3 / a.tp * (1 + ar * k):.0f}** "
            f"tok/s/chip at the measured acceptance.")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--attribution", default=None,
                    help="decode-step-attribution JSON; overrides step/"
                         "weights/attention defaults with its measurements")
    ap.add_argument("--measured-json", default=None,
                    help="bench artifact (BENCH_rNN.json or a bare "
                         "--phase tp7b dict) carrying the measured "
                         "sharded-step sweep (gemma_7b.tp_sweep); adds "
                         "the measured re-pricing section")
    ap.add_argument("--measured-step", type=float, default=None,
                    help="one measured sharded step in ms (with "
                         "--measured-bs) instead of --measured-json")
    ap.add_argument("--measured-bs", type=int, default=192)
    ap.add_argument("--acceptance", default=None,
                    help="bench artifact carrying a measured spec "
                         "acceptance ratio (spec_sweep or "
                         "tp_spec_sweep); adds the Spec×TP composed "
                         "section — the TP step price x the measured "
                         "acceptance (ISSUE 18)")
    ap.add_argument("--draft-step-ratio", type=float, default=0.27,
                    help="draft step cost as a fraction of the "
                         "target's (2B int8 weight stream ~2.5 GB vs "
                         "the 7B's 9.35 GB; both shard by tp, so the "
                         "ratio survives the mesh)")
    ap.add_argument("--measured-allreduce", type=float, default=None,
                    help="measured all-reduce ms within the sharded "
                         "step (attribution category; default: the "
                         "priced ring model)")
    ap.add_argument("--step-ms", type=float, default=33.3,
                    help="measured single-chip step (r5 trace, bs=48)")
    ap.add_argument("--weights-ms", type=float, default=11.6)
    ap.add_argument("--attn-ms", type=float, default=2.5)
    ap.add_argument("--bs", type=int, default=48,
                    help="batch the step was measured at")
    ap.add_argument("--tp", type=int, default=8)
    ap.add_argument("--layers", type=int, default=28)
    ap.add_argument("--dim", type=int, default=3072)
    ap.add_argument("--dtype-bytes", type=int, default=2)
    ap.add_argument("--ici-gbps", type=float, default=45.0,
                    help="effective per-hop ICI bandwidth (ASSUMPTION)")
    ap.add_argument("--ici-latency-us", type=float, default=1.0,
                    help="per-hop collective latency (ASSUMPTION)")
    ap.add_argument("--hbm-gb", type=float, default=16.0)
    ap.add_argument("--reserve-gb", type=float, default=1.5)
    ap.add_argument("--weights-gb", type=float, default=9.35)
    ap.add_argument("--kv-mb-per-slot", type=float, default=47.7,
                    help="int8 KV bytes per slot at S_alloc=208 "
                         "(28L×208×16×256×2)")
    ap.add_argument("--kv-pool", choices=["on", "off"], default="on",
                    help="block-paged KV accounting (ISSUE 10): slots "
                         "pay only their live, unshared pages; off = "
                         "the dense per-slot S_alloc regions")
    ap.add_argument("--s-alloc", type=int, default=208,
                    help="allocated rows per slot the dense layout pays")
    ap.add_argument("--avg-tokens", type=int, default=144,
                    help="measured average live rows per slot (prompt + "
                         "generated) the pool actually allocates — the "
                         "kubectl workload's bench median (~80 prompt + "
                         "64 budget)")
    ap.add_argument("--shared-prefix-tokens", type=int, default=64,
                    help="radix-shared prefix rows (system prompt + "
                         "reused history) counted once, not per slot")
    ap.add_argument("--g", type=float, default=0.5,
                    help="fraction of the residual that scales with batch "
                         "(per-slot work: KV writes, sampling rows; the "
                         "attribution table pins this)")
    ap.add_argument("--f-list", default="0.0,0.5,1.0",
                    help="residual TP-shardable fractions to sweep")
    ap.add_argument("--batch-list", default="48,128,192,256")
    a = ap.parse_args()
    a.f_list = [float(x) for x in a.f_list.split(",")]
    a.batch_list = [int(x) for x in a.batch_list.split(",")]
    a.kv_pool = a.kv_pool == "on"

    if a.attribution:
        with open(a.attribution) as f:
            att = json.load(f)
        cats = {c["name"]: c["ms_per_step"] for c in att["categories"]}
        a.step_ms = att["step_ms"]
        a.weights_ms = cats.get("weight_gemms", a.weights_ms)
        a.attn_ms = cats.get("attention", a.attn_ms)
        a.bs = att.get("batch_size", a.bs)
        print(f"# inputs from {a.attribution} "
              f"(coverage {att.get('coverage_pct')}%)", file=sys.stderr)

    out = project(a)
    print(render(a, out))

    rungs = []
    if a.measured_json:
        with open(a.measured_json) as f:
            bench = json.load(f)
        sweep = bench
        for key in ("gemma_7b", "tp_sweep"):
            if isinstance(sweep, dict) and key in sweep:
                sweep = sweep[key]
        if isinstance(sweep, dict):
            rungs = [r for r in sweep.get("rungs", ())
                     if isinstance(r, dict) and "step_ms" in r]
        if not rungs:
            print(f"# no tp_sweep rungs in {a.measured_json}",
                  file=sys.stderr)
    elif a.measured_step is not None:
        rungs = [{"bs": a.measured_bs, "step_ms": a.measured_step,
                  "allreduce_ms": a.measured_allreduce}]
    if rungs:
        for r in rungs:
            # Only an ABSENT measurement falls back to the priced ring
            # model — a measured 0.0 (attribution billed no comm) must
            # stay 0.0, or the "measured" table silently mixes in
            # priced values.
            if r.get("allreduce_ms") is None:
                r["allreduce_ms"] = a.layers * 2 * allreduce_ms(
                    a.tp, int(r["bs"]) * a.dim * a.dtype_bytes,
                    a.ici_gbps, a.ici_latency_us)
        print(render_measured(a, rungs))

    if a.acceptance:
        with open(a.acceptance) as f:
            acc = extract_acceptance(json.load(f))
        if acc is None:
            print(f"# no spec_sweep/tp_spec_sweep acceptance in "
                  f"{a.acceptance}", file=sys.stderr)
        else:
            print(render_acceptance(a, acc, rungs, out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
