#!/usr/bin/env python3
"""The benchmark's server child: registers the cell's configuration with the
program's model registry, waits for the comparison with the plain reference
(refcheck.py, the other child) to release the chip, then runs the real server
(``ai_agent_kubectl_tpu.server.main``) in this same process.

Benchmark code, not program code: nothing here is imported by the program.
What it leaves in ``--run-dir`` for the parent (which never imports jax):

- ``model_config.json``  the ModelConfig the registry now holds under the name;
- ``compiles.log``       one line ``<unix time> <seconds>`` per backend compile;
- ``memory.json``        every local device's ``memory_stats()`` and, at the top,
                         those of the fullest one; rewritten every second.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

from modelmap import fold_seed, key_map, model_config, sizes  # noqa: E402


def register(cfg_file: dict):
    """``models.config._register`` the configuration under its name, and check
    that every key of the file reached the ModelConfig the engine will read."""
    from ai_agent_kubectl_tpu.models import config as mc

    sz, kmap = sizes(cfg_file), key_map(cfg_file)
    mc._register(model_config(cfg_file["name"], sz, kmap))
    got = dataclasses.asdict(mc.get_config(cfg_file["name"]))
    for key, field in kmap.items():
        if key in sz and got[field] != sz[key]:
            raise SystemExit(f"serve: {key}={sz[key]!r} did not reach "
                             f"ModelConfig.{field} ({got[field]!r})")
    return mc.get_config(cfg_file["name"]), sz


def make_weights_in_one_call() -> None:
    """The engine makes seeded int8 weights leaf by leaf, eagerly: ~150 small
    device programs, and a stack of 48 expert slices whose transients do not
    fit beside two finished expert leaves on a 16 GB chip (Mixtral at 6
    layers ran out of memory there, PERF.md PR 23). Here the program's own
    generator runs as ONE jitted call from the key — same function, same
    values, 0.01 GiB of temporaries (AOT, PR 23). The one call runs slower
    than the eager slices (~5 s a GB against ~1.4), so only a configuration
    whose file sets ``weights_in_one_call`` takes it."""
    import jax
    from ai_agent_kubectl_tpu.ops import quant

    leaf_by_leaf = quant.random_params_int8

    def in_one_call(key, cfg, dtype=None, quantize_embed=False, int4=False):
        make = jax.jit(lambda k: leaf_by_leaf(k, cfg, dtype=dtype,
                                              quantize_embed=quantize_embed, int4=int4))
        return make(key)

    quant.random_params_int8 = in_one_call


def answers_run_to_the_cap() -> None:
    """A serving benchmark fixes the output length ("ignore EOS"). With seeded
    random weights, whether end-of-sequence wins a greedy step at a place where
    the grammar allows the command to end is a lottery of the seed: most seeds
    never end an answer early, one in ten ended a third of them (93 tokens a
    request against 128; PERF.md, PR 23), and the seed must not change the
    work. The program has no such switch, so the grammar's token tables are
    edited as they are compiled: EOS stays legal only where it is the ONLY
    legal token (the command cannot go on). Everything else of the grammar,
    and the cost of masking, is as shipped."""
    from ai_agent_kubectl_tpu.constrain import fsm, runtime

    compile_as_shipped = fsm.compile_token_fsm

    def compile_without_early_eos(*args, **kwargs):
        tables = compile_as_shipped(*args, **kwargs)
        for eos in tables.eos_ids:
            tables.class_ok[:, tables.tok_class[eos]] = tables.forced_eos
        return tables

    fsm.compile_token_fsm = runtime.compile_token_fsm = compile_without_early_eos


def watch_compiles(path: Path) -> None:
    import jax.monitoring

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with open(path, "a") as f:
                f.write(f"{time.time():.3f} {seconds:.3f}\n")

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def report_memory(path: Path) -> None:
    import jax

    keys = ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")

    def loop() -> None:
        devices = jax.local_devices()
        while True:
            each = [{k: (d.memory_stats() or {}).get(k) for k in keys} for d in devices]
            fullest = max(each, key=lambda s: s["peak_bytes_in_use"] or 0)
            tmp = path.with_suffix(".tmp")
            tmp.write_text(json.dumps({**fullest, "devices": each}))
            os.replace(tmp, path)
            time.sleep(1.0)

    threading.Thread(target=loop, name="bench-memory", daemon=True).start()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--after", required=True,
                    help="start the server once this file exists")
    args = ap.parse_args()
    run_dir = Path(args.run_dir)
    cfg_file = json.loads(Path(args.config).read_text())
    seed = fold_seed(args.seed)

    watch_compiles(run_dir / "compiles.log")
    import jax
    jax.config.update("jax_log_compiles", True)    # server.log names each program
    if args.rehearse:
        # a toy model the program registers (run.py::rehearsal_model); the
        # file's own sizes still go through the mapping and the reference, at
        # widths a CPU can run.
        from ai_agent_kubectl_tpu.models.config import get_config
        model_cfg = get_config(os.environ["MODEL_NAME"])
    else:
        model_cfg, _ = register(cfg_file)
    (run_dir / "model_config.json").write_text(
        json.dumps(dataclasses.asdict(model_cfg)))

    # Everything above touched no device. The chip belongs to one process at
    # a time: wait until the parent says that the comparison with the
    # reference (refcheck.py, a process of its own, so that nothing of it
    # stays in device memory) has ended.
    while not os.path.exists(args.after):
        time.sleep(0.05)

    # The server builds its engine from the environment; the weights' seed is
    # the one thing it does not read from there, so it is set on the engine
    # it built, before start() makes the weights.
    from ai_agent_kubectl_tpu.server import __main__ as server_main
    build = server_main.build_engine

    def build_seeded(cfg):
        engine = build(cfg)
        if not hasattr(engine, "seed"):
            raise SystemExit(f"serve: engine {engine!r} takes no seed")
        engine.seed = seed
        return engine

    server_main.build_engine = build_seeded
    if cfg_file.get("weights_in_one_call"):
        make_weights_in_one_call()
    answers_run_to_the_cap()
    report_memory(run_dir / "memory.json")
    server_main.main()


if __name__ == "__main__":
    main()
