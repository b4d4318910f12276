#!/usr/bin/env python3
"""Did a change move another configuration's programs? A hash of the OPTIMIZED
HLO text (the CPU compiler's, here, no chip) of each toy preset's pool
``forward``, one decode pass and one 32-wide packed window (``--widths``), gathered and through
the interpreted ragged kernel, less what names a source line (op metadata, the
stack-frame tables, the checkout's path). Run it on two checkouts and compare:

    git archive <parent> | tar -x -C /root/scratch/parent
    python tools/hlo_hash.py --package-root /root/scratch/parent > a.json
    python tools/hlo_hash.py > b.json && diff a.json b.json

One JSON object: ``{"<model>/<impl>/W<width>": [sha256's first 16, the text's
length]}``; ``{"<model>": null}`` for a preset the checkout does not have.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import re
import sys
from pathlib import Path

MODELS = ("toy-8m", "toy-sparse-moe", "toy-hybrid-moe", "toy-mla-moe",
          "toy-sliding-moe", "toy-linear-hybrid", "toy-kda-mla-moe",
          "toy-gdn-moe")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--package-root", default=str(Path(__file__).resolve().parents[1]))
    ap.add_argument("--models", nargs="+", default=list(MODELS))
    ap.add_argument("--widths", type=int, nargs="+", default=[1, 32],
                    help="window widths (1: a decode pass; wider: packed rows)")
    args = ap.parse_args()
    root = str(Path(args.package_root).resolve())
    sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import (KVCache, forward,
                                                         init_params)

    B, page, pages = 4, 16, 8
    sds = jax.ShapeDtypeStruct
    out = {}
    for name in args.models:
        try:
            cfg = get_config(name)
        except KeyError:        # a checkout from before the preset
            out[name] = None
            continue
        params = jax.eval_shape(lambda k: init_params(k, cfg, jnp.float32),
                                jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: KVCache.pool_zeros(
            cfg, n_blocks=B * pages, page=page, slots=B,
            ring=cfg.sliding_ring(64, page), dtype=jnp.float32,
            counts_experts=cfg.grouped_experts))
        for impl in ("dense", "ragged"):
            for W in args.widths:
                def step(params, tok, pos, cache, wmask, tables, q_lens):
                    return forward(
                        params, cfg, tok, pos, cache, kv_limit=pages * page,
                        attn_impl=impl, token_mask=wmask, write_mask=wmask,
                        block_tables=tables, q_lens=q_lens,
                        logits_at=jnp.maximum(q_lens, 1) - 1,
                        packed_rows=None if W == 1 else W + B)

                text = jax.jit(step).lower(
                    params, sds((B, W), jnp.int32), sds((B, W), jnp.int32),
                    cache, sds((B, W), jnp.bool_), sds((B, pages), jnp.int32),
                    sds((B,), jnp.int32)).compile().as_text()
                text = re.sub(r", metadata=\{[^}]*\}", "", text).replace(root, "")
                text = "\n".join(l for l in text.splitlines()
                                 if not re.match(r"^\d+ (\{|\")", l))
                out[f"{name}/{impl}/W{W}"] = (
                    hashlib.sha256(text.encode()).hexdigest()[:16], len(text))
    print(json.dumps(out, indent=0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
