"""Compile the cells' device programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python3 chipbench/compile_check.py

Compiles, at the configurations' real sizes, the program's jitted
training step, the reference's training step (float32 and the fp8
control) and the incrementation's ``+1`` over one 617 MiB block, and
prints each program's ``memory_analysis``. A compile that passes is not a
chip run: it says the TPU compiler takes the program and how much device
memory one program needs, nothing about time or results.
"""

from __future__ import annotations

import json
import os
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.harness import load_json
    from chipbench.reference import granite as ref
    from repro.launch.train import build_model_config, parse_args, train_config
    from repro.training.train_step import make_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    def report(name, compiled):
        m = compiled.memory_analysis()
        fields = ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
                  "alias_size_in_bytes", "generated_code_size_in_bytes")
        out = {f: int(getattr(m, f)) for f in fields}
        out["total_gib"] = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                            + out["temp_size_in_bytes"] - out["alias_size_in_bytes"]) / 2**30
        print(json.dumps({"program": name, **out}), flush=True)

    cfg = load_json(os.path.join(ROOT, "chipbench/configs/granite-3-2b-8L-train.json"))
    tc = cfg["train"]
    args = parse_args(["--arch", cfg["program"]["arch"], "--n-layers", str(cfg["num_hidden_layers"]),
                       "--batch", str(tc["batch"]), "--seq", str(tc["seq"]),
                       "--steps", str(tc["steps"]), "--lr", repr(tc["lr"])])
    mcfg = build_model_config(args)
    init_state, train_step, _ = make_train_step(mcfg, train_config(args, mcfg))
    state = shaped(jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    batch = {k: jax.ShapeDtypeStruct((tc["batch"], tc["seq"]), jnp.int32, sharding=one)
             for k in ("tokens", "labels")}
    report("program train step", jax.jit(train_step, donate_argnums=0).lower(state, batch).compile())

    specs = ref.param_specs(cfg)
    p = {k: jax.ShapeDtypeStruct(s[0], jnp.dtype(s[1]), sharding=one) for k, s in specs.items()}
    f32 = {k: jax.ShapeDtypeStruct(s[0], jnp.float32, sharding=one) for k, s in specs.items()}
    rstate = {"p": p, "m": f32, "v": f32,
              "t": jax.ShapeDtypeStruct((), jnp.int32, sharding=one)}
    toks = jax.ShapeDtypeStruct((tc["batch"], tc["seq"]), jnp.int32, sharding=one)
    for mode in ("f32", "fp8"):
        step = jax.jit(partial(ref.train_step, cfg=cfg, mode=mode), donate_argnums=0)
        report(f"reference train step ({mode})", step.lower(rstate, toks, toks).compile())

    icfg = load_json(os.path.join(ROOT, "chipbench/configs/incr-bigbrain-617MiB.json"))
    x = jax.ShapeDtypeStruct((icfg["block_elems"],), jnp.float32, sharding=one)
    c = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    report("incrementation +1", jax.jit(lambda x, c: x + c).lower(x, c).compile())
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
