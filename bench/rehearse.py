"""Compile a configuration's two step programs for a described TPU v5e and
print what they need in device memory; nothing runs.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <config> [--slots N] [--chunk C]

The step is the engine's jitted ``paged_step`` over the served parameters
(every linear a compiled W4A4+LRC kernel) with the cell's pool geometry:
one ``(1, prefill_chunk)`` chunk and one ``(slots, 1)`` decode step.  The
pool argument is not donated, so a step's output holds a second pool.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.model import engine, family, load_spec
    from repro.kernels.context import KernelContext
    from repro.models import model as model_lib
    from repro.quant.qlinear import retag_qlinear_impl
    from repro.serve.kvquant import KVSpec

    spec = load_spec(args.config)
    if args.slots:
        spec = dataclasses.replace(spec, slots=args.slots)
    if args.chunk:
        spec = dataclasses.replace(spec, prefill_chunk=args.chunk)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    fam = engine(spec.reference)
    cfg = fam.program_config(spec)
    w = jax.eval_shape(family(spec.reference).make_weights, spec,
                       jax.random.PRNGKey(0))
    params = retag_qlinear_impl(fam.program_params(spec, w), "pallas",
                                ctx=KernelContext(interpret=False))
    params = jax.tree.map(sds, params)
    per_slot = -(-spec.max_seq // spec.page_size)
    kv = KVSpec(dtype=spec.kv_dtype)
    pool = jax.eval_shape(lambda: model_lib.init_paged_cache(
        cfg, spec.slots * per_slot + 1, spec.page_size, kv_spec=kv))
    pool = jax.tree.map(sds, pool)

    def step(params, tokens, positions, valid, cache, block_table, srow):
        return model_lib.paged_step(cfg, params, tokens, positions, valid,
                                    cache, block_table, srow, kv_spec=kv)

    gib = 2 ** 30
    for name, (b, s) in (("decode", (spec.slots, 1)),
                         ("chunk", (1, spec.prefill_chunk))):
        i32 = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(
            shape, dt, sharding=chip)
        compiled = jax.jit(step).lower(
            params, i32((b, s)), i32((b, s)), i32((b, s), jnp.bool_), pool,
            i32((b, per_slot)), i32((b,))).compile()
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{spec.name} {name} ({b}, {s}), {spec.slots} slots: "
              f"arguments {m.argument_size_in_bytes / gib:.2f} GiB, "
              f"outputs {m.output_size_in_bytes / gib:.2f} GiB, "
              f"temporaries {m.temp_size_in_bytes / gib:.2f} GiB, "
              f"aliased {m.alias_size_in_bytes / gib:.2f} GiB, "
              f"total {total / gib:.2f} GiB; kernels "
              f"{compiled.as_text().count('tpu_custom_call')}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
