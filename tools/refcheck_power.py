#!/usr/bin/env python3
"""Has the benchmark's comparison with the plain reference power over what a
configuration adds? (ISSUE 31; written for keye-vl-2.0-30b-a3b-l8, extended by
ISSUE 33 for nemotron-3-nano-30b-a3b-l13.)

Runs benchmark/refcheck.py's OWN comparison (``refcheck.run``: its shapes, its
seeded weights, its window-then-decode program path, its rule and its verdict,
all from the configuration's ``reference_check``) over several seeds, for the
shipped pair and for two pairs that must read ``ok: false``:

- ``every_key``: the program against the reference with ``topk`` past any
  context — the selector left out of the mathematics;
- ``w8a8``: the program with 8-bit activations (ops/quant.py::to_w8a8)
  against the shipped reference — the nearest precision below bf16;
- ``bf16_state`` (a configuration with state-space or linear-attention
  layers): the program with its recurrent state carried in bf16 (ops/
  ssd_scan.py::STATE_DTYPE, ops/gated_delta.py::STATE_DTYPE) instead
  of float32, and rounded at every token as decoding rounds it (the scan's
  chunk set to 1: inside a chunk the state is never formed, so at the
  served chunk of 128 a 900-token prompt would round it 7 times where 900
  decode steps round it 900 times).

``--continued a b`` runs, beside them, the comparison ``refcheck.run`` cannot
(it may not be edited and makes ONE window): the same two prompts, then a
SECOND ragged window that continues row 0 by ``a`` tokens and row 1 by ``b``
from the state and the K/V the first left, then the decode steps — every
position against the reference under the file's own rule
(``run_continued``; lines ``"what": "<variant>+continued"``).

``--long n [--long-rows r] [--long-window w]`` runs that comparison at the
contexts the cell serves and ``refcheck.run`` cannot reach (it keeps every
position's logits of ONE window: 24 rows of 384 are 4.8 GB, and it needs the
24 rows for a clear position): ``r`` sequences of about ``n`` tokens prefilled
in ``w``-wide windows as the engine prefills them, each window from the state
the last one left, then the decode steps; the group median as a whole and by
position (``by_position``), so that a reading says from which context length
a control is told apart (lines ``"what": "<variant>+long"``).
``--positions file`` dumps the file's own shape position by position (error,
and the reference's ``aux``: the margins a rule for ``clear_if`` is fitted
to); ``--prompts`` and ``--decode-steps`` put another shape in its place, such
as short prompts and many decode steps.
``bf16_state_chunked`` is the bf16 state as a long prefill would round it: at
the served chunk's edges and at decode steps, not at every token.

The controls change what ``refcheck.run`` is handed, not the comparison: the
reference module it loads is wrapped for the first, the ``forward`` it imports
for the second. One line a run: refcheck's result (worst clear position, the
group's median, both as shares of the reference logits' deviation, the
tolerance, ``ok``). A tolerance is set between the largest shipped reading
and the smallest of the two others (the file's ``tolerance_why`` has them).

    chiprun -- python tools/refcheck_power.py --config benchmark/configs/<name>.json --seeds 11 12 13
    python tools/refcheck_power.py --config ... --seeds 1 --rehearse     # CPU, toy widths
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))
sys.path.insert(1, str(ROOT))

VARIANTS = ("shipped", "every_key", "w8a8", "bf16_state", "bf16_state_chunked")


def variants_of(cfg_file: dict) -> list:
    """The controls that mean something for this configuration."""
    out = ["shipped", "w8a8"]
    if "topk" in cfg_file:
        out.insert(1, "every_key")
    if any(key in cfg_file for key in ("ssm_state_size", "mamba_d_state",
                                       "linear_key_head_dim")):
        out.append("bf16_state")
    return out


def long_schedule(n: int, rows: int, window: int) -> list:
    """``rows`` sequences of ``n``, ``n`` - 97, ``n`` - 194... tokens as
    windows of at most ``window``: every row has tokens in every window."""
    left = [n - 97 * b for b in range(rows)]
    if min(left) <= (n - 1) // window * window:
        raise SystemExit("--long: too many rows for a last window of this width")
    out = []
    while max(left) > 0:
        out.append([min(window, x) for x in left])
        left = [x - q for x, q in zip(left, out[-1])]
    return out


def run_continued(refcheck, cfg_file: dict, sz: dict, seed: int, more: list,
                  rehearse: bool, schedule: list = None, window: int = None,
                  positions: list = None) -> dict:
    """``refcheck.run``'s comparison with a second window: prompts of
    ``reference_check.prompt_tokens`` in one ragged window, then row b
    continued by ``more[b]`` tokens in another (a window that STARTS from a
    carried recurrent state and a filled pool), then the decode steps. Same
    seeded weights, same reference, same two-group rule and tolerance.
    With ``schedule`` (``long_schedule``) the windows are those, ``window``
    wide, in place of the file's prompts and ``more``. ``positions``, a list,
    is given a row's every position: its error as a share of the deviation
    and what the reference's ``aux`` says of it. What has been done so far
    goes to stderr, so that a call cut at its limit says where it was."""
    said = lambda *what: print("power:", *what, file=sys.stderr, flush=True)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ai_agent_kubectl_tpu.models import transformer
    from ai_agent_kubectl_tpu.ops.quant import random_params_int8
    from modelmap import key_map, model_config

    chk = cfg_file["reference_check"]
    sz = dict(sz, num_hidden_layers=chk["layers"])
    if rehearse:
        sz.update(refcheck.REHEARSAL_SIZES)
    cfg = model_config("refcheck", sz, key_map(cfg_file))
    params = random_params_int8(jax.random.PRNGKey(seed), cfg, dtype=jnp.bfloat16,
                                quantize_embed=True)
    lens, W, steps, PAGE = list(chk["prompt_tokens"]), chk["window"], chk["decode_steps"], refcheck.PAGE
    if schedule is None:
        schedule = [lens, more]
    else:
        lens, W = schedule[0], window
    B = len(lens)
    totals = [sum(q[b] for q in schedule) for b in range(B)]
    toks = np.random.default_rng(seed).integers(
        3, min(cfg.vocab_size, 1337), size=(B, max(totals) + steps), dtype=np.int32)
    pages = -(-(max(totals) + steps) // PAGE)
    pool = (cfg.n_layers, B * pages, PAGE, cfg.kv_heads_paged, cfg.head_dim)
    cache = transformer.KVCache(k=jnp.zeros(pool, jnp.bfloat16), v=jnp.zeros(pool, jnp.bfloat16),
                                lengths=jnp.zeros((B * pages,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)

    @jax.jit
    def step(params, tok, pos, cache, wmask, q_lens):
        return transformer.forward(params, cfg, tok, pos, cache, kv_limit=pages * PAGE,
                                   attn_impl="ragged", token_mask=wmask, write_mask=wmask,
                                   page_size=PAGE, block_tables=tables, q_lens=q_lens)

    done, got = np.zeros(B, np.int32), [[] for _ in range(B)]
    for q in schedule + [[1] * B] * steps:
        q = np.asarray(q, np.int32)
        w = W if q.max() > 1 else 1
        tok = np.zeros((B, w), np.int32)
        for b in range(B):
            tok[b, :q[b]] = toks[b, done[b]:done[b] + q[b]]
        pos = (done[:, None] + np.arange(w)[None, :]).astype(np.int32)
        logits, cache = step(params, jnp.asarray(tok), jnp.asarray(pos), cache,
                             jnp.asarray(np.arange(w)[None, :] < q[:, None]), jnp.asarray(q))
        for b in range(B):
            got[b].append(np.asarray(logits[b, :q[b]]))
        done += q
        if w > 1:
            said("the program's window to", done.tolist())
    said("the program's", steps, "decode steps")
    ref = refcheck.load_reference(cfg_file["reference"])
    weights_of = refcheck.weights_function(ref)
    ref_forward = jax.jit(lambda p, t: ref.forward(sz, weights_of(p, cfg.n_layers), t))
    rule = chk.get("clear_if")
    clear_errs, unclear_errs, stds, second, where, worst = [], [], [], [], [], []
    for b in range(B):
        n = totals[b] + steps
        want, aux = ref_forward(params, jnp.asarray(toks[b]))
        want = np.asarray(want)[:n]
        err = np.abs(np.concatenate(got[b]) - want).max(axis=1)
        clear = (np.asarray(aux[rule["aux"]])[:n] >= rule["min"]) if rule else np.ones(n, bool)
        clear_errs.append(err[clear])
        unclear_errs.append(err[~clear])
        second.append(err[lens[b]:])                # the later windows and the decode steps
        # (without a rule every position is clear: the medians by position
        # are then over all of them)
        where.append(np.arange(n)[~clear] if rule else np.arange(n))
        stds.append(float(want.std()))
        worst += [(float(err[i]), b, int(i)) for i in np.argsort(err)[-3:]]
        said("the reference's row", b, "of", n, "tokens")
        if positions is not None:
            positions.append({"prompt": lens[b], "err": (err / stds[-1]).round(5).tolist(), **{
                name: np.asarray(a)[:n].round(6).tolist() for name, a in aux.items()}})
    std = float(np.mean(stds))
    clear_errs, unclear_errs = np.concatenate(clear_errs), np.concatenate(unclear_errs)
    rel = float(clear_errs.max()) / std if clear_errs.size else float("nan")
    rel_unclear = float(np.median(unclear_errs)) / std if unclear_errs.size else None
    second, where = np.concatenate(second), np.concatenate(where)
    group = unclear_errs if rule else clear_errs
    edges = [0, 256, 1024, 2048, 4096, 8192, 1 << 30]
    by_position = {f"{lo}-{hi if hi < 1 << 30 else ''}": float(np.median(group[sel])) / std
                   for lo, hi in zip(edges, edges[1:])
                   if (sel := (where >= lo) & (where < hi)).any()}
    ok = bool((not clear_errs.size or rel <= chk["tolerance_rel"])
              and (rel_unclear is None or rel_unclear <= chk["tolerance_rel"]))
    return {"ok": ok, "rel_err": rel, "rel_err_unclear_median": rel_unclear,
            "rel_err_median": float(np.median(clear_errs)) / std if clear_errs.size else None,
            "rel_err_second_window_median": float(np.median(second)) / std,
            "rel_err_second_window_p99": float(np.percentile(second, 99)) / std,
            "rel_err_unclear_median_by_position": by_position,
            "tolerance_rel": chk["tolerance_rel"], "decode_steps": steps,
            "windows": schedule if len(schedule) <= 2 else [len(schedule), W, totals],
            # where the largest errors are: [share of the deviation, row, position]
            "worst_positions": [[round(e / std, 3), b, i] for e, b, i in sorted(worst)[-5:]],
            "positions_clear": int(clear_errs.size), "positions_unclear": int(unclear_errs.size),
            "layers": cfg.n_layers, "platform": jax.devices()[0].platform}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", nargs="*", default=None, choices=VARIANTS)
    ap.add_argument("--continued", type=int, nargs="*", default=None,
                    help="tokens a second window continues each prompt by")
    ap.add_argument("--long", type=int, default=0,
                    help="context length of the long comparison (0: not run)")
    ap.add_argument("--long-rows", type=int, default=2)
    ap.add_argument("--long-window", type=int, default=512)
    ap.add_argument("--long-only", action="store_true",
                    help="skip refcheck.run's own comparison")
    ap.add_argument("--layers", type=int, default=0,
                    help="compare at this depth, not reference_check.layers")
    ap.add_argument("--prompts", type=int, nargs="*", default=None,
                    help="these prompts in the one window, not reference_check.prompt_tokens")
    ap.add_argument("--decode-steps", type=int, default=0,
                    help="this many decode steps, not reference_check.decode_steps")
    ap.add_argument("--positions", default="",
                    help="write every position's error and aux of the file's own shape "
                         "(one window, then the decode steps) to this file, a line a run")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "refcheck_power.jsonl"))
    args = ap.parse_args()

    import refcheck
    import jax.numpy as jnp
    from ai_agent_kubectl_tpu.models import transformer
    from ai_agent_kubectl_tpu.ops import gated_delta, ssd_scan
    from ai_agent_kubectl_tpu.ops.quant import to_w8a8
    from modelmap import fold_seed, key_map, sizes

    if not args.rehearse:
        # refcheck.main's own rule for the compile cache: every run of a
        # variant after its first loads what the first compiled
        import os

        import jax
        from ai_agent_kubectl_tpu.config import DEFAULT_COMPILE_CACHE_DIR
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    cfg_file = json.loads(Path(args.config).read_text())
    if args.layers:
        cfg_file["reference_check"]["layers"] = args.layers
    if args.prompts:
        cfg_file["reference_check"].update(prompt_tokens=args.prompts, batch=len(args.prompts))
    if args.decode_steps:
        cfg_file["reference_check"]["decode_steps"] = args.decode_steps
    only = args.only or variants_of(cfg_file)
    load_reference, forward = refcheck.load_reference, transformer.forward
    state_dtype, lin_chunk = ssd_scan.STATE_DTYPE, gated_delta.CHUNK

    def every_key_reference(path):
        ref = load_reference(path)
        wrapped = types.SimpleNamespace(**vars(ref))
        wrapped.forward = lambda cfg, w, t: ref.forward(dict(cfg, topk=10 ** 9), w, t)
        return wrapped

    def w8a8_forward(params, *a, **kw):
        return forward(to_w8a8(params), *a, **kw)

    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as out:
        for seed in args.seeds:
            for what in only:
                refcheck.load_reference = every_key_reference if what == "every_key" else load_reference
                transformer.forward = w8a8_forward if what == "w8a8" else forward
                # the linear layers' scan in chunks of ONE token: the state is
                # formed, and rounded, at every token (the state-space layers'
                # chunk is a key of the file, set below)
                gated_delta.CHUNK = 1 if what == "bf16_state" else lin_chunk
                ssd_scan.STATE_DTYPE = gated_delta.STATE_DTYPE = (
                    jnp.bfloat16 if what.startswith("bf16_state") else state_dtype)
                sz = sizes(cfg_file)
                if what == "bf16_state":
                    # the file's own name for the state-space scan's chunk
                    for key, field in key_map(cfg_file).items():
                        if field == "ssm_chunk" and key in sz:
                            sz[key] = 1
                try:
                    results = {}
                    if not args.long_only:
                        results[what] = refcheck.run(cfg_file, sz, fold_seed(seed),
                                                     rehearse=args.rehearse)
                    if args.continued:
                        results[what + "+continued"] = run_continued(
                            refcheck, cfg_file, sz, fold_seed(seed),
                            args.continued, args.rehearse)
                    if args.positions:
                        chk, rows = cfg_file["reference_check"], []
                        results[what + "+positions"] = run_continued(
                            refcheck, cfg_file, sz, fold_seed(seed), None, args.rehearse,
                            [list(chk["prompt_tokens"])], chk["window"], rows)
                        with open(args.positions, "a") as f:
                            f.write(json.dumps({"seed": seed, "what": what, "rows": rows}) + "\n")
                    if args.long:
                        results[what + "+long"] = run_continued(
                            refcheck, cfg_file, sz, fold_seed(seed), None, args.rehearse,
                            long_schedule(args.long, args.long_rows, args.long_window),
                            args.long_window)
                finally:
                    refcheck.load_reference, transformer.forward = load_reference, forward
                    ssd_scan.STATE_DTYPE = gated_delta.STATE_DTYPE = state_dtype
                    gated_delta.CHUNK = lin_chunk
                for name, res in results.items():
                    line = json.dumps({"seed": seed, "what": name, **res})
                    print("power: " + line, flush=True)
                    out.write(line + "\n")
                    out.flush()


if __name__ == "__main__":
    main()
