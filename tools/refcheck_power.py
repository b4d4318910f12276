#!/usr/bin/env python3
"""Has the benchmark's comparison with the plain reference power over what a
configuration adds? (ISSUE 31; written for keye-vl-2.0-30b-a3b-l8.)

Runs benchmark/refcheck.py's OWN comparison (``refcheck.run``: its shapes, its
seeded weights, its window-then-decode program path, its rule and its verdict,
all from the configuration's ``reference_check``) over several seeds, for the
shipped pair and for two pairs that must read ``ok: false``:

- ``every_key``: the program against the reference with ``topk`` past any
  context — the selector left out of the mathematics;
- ``w8a8``: the program with 8-bit activations (ops/quant.py::to_w8a8)
  against the shipped reference — the nearest precision below bf16.

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

VARIANTS = ("shipped", "every_key", "w8a8")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", nargs="*", default=list(VARIANTS), choices=VARIANTS)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "refcheck_power.jsonl"))
    args = ap.parse_args()

    import refcheck
    from ai_agent_kubectl_tpu.models import transformer
    from ai_agent_kubectl_tpu.ops.quant import to_w8a8
    from modelmap import fold_seed, sizes

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
    load_reference, forward = refcheck.load_reference, transformer.forward

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
            for what in args.only:
                refcheck.load_reference = every_key_reference if what == "every_key" else load_reference
                transformer.forward = w8a8_forward if what == "w8a8" else forward
                try:
                    res = refcheck.run(cfg_file, sizes(cfg_file), fold_seed(seed),
                                       rehearse=args.rehearse)
                finally:
                    refcheck.load_reference, transformer.forward = load_reference, forward
                line = json.dumps({"seed": seed, "what": what, **res})
                print("power: " + line, flush=True)
                out.write(line + "\n")
                out.flush()


if __name__ == "__main__":
    main()
