"""Memory of each cell's decode step and largest prefill, compiled for one
chip of a described TPU v5e (no chip needed; nothing runs).

    JAX_PLATFORMS=cpu python3 benchmarks/chip/size_pool.py [cell ...]

For each cell it builds the served model's weights and the KV pool of the
traffic file's ``pool_blocks`` as shapes, compiles the engine's decode step
(gather attention, a full batch) and the prefill of the longest prompt, and
prints ``compiled.memory_analysis()``: arguments, outputs, temporaries.  The
pool is not donated, so a step holds it twice; the budget is weights +
2 x pool + temporaries, which has to stay under the chip's ``bytes_limit``
with a margin.  The numbers go into each traffic file's ``sizing``.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import replace
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def analyse(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"arguments": ma.argument_size_in_bytes,
            "outputs": ma.output_size_in_bytes,
            "temporaries": ma.temp_size_in_bytes,
            "aliased": ma.alias_size_in_bytes,
            "total": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                      + ma.temp_size_in_bytes - ma.alias_size_in_bytes)}


def cell_sizes(cell: dict, one_chip) -> dict:
    import jax
    import jax.numpy as jnp
    import traffic as traffic_mod
    from repro.configs.base import get_config
    from repro.models import PagedLayout, cache_spec, model_spec
    from repro.models.common import BF16, ParamSpec
    from repro.models.decode import decode_step, prefill_step

    cfile = json.loads((HERE / "configs" / f"{cell['config']}.json")
                       .read_text())
    tr = traffic_mod.load(cell["traffic"])
    cfg = replace(get_config(cfile["arch"]),
                  n_layers=cfile["num_hidden_layers"])
    eng = cfile["engine"]
    B, bt = eng["max_batch"], eng["block_tokens"]
    layout = PagedLayout(num_blocks=tr["pool_blocks"], block_tokens=bt,
                         max_blocks=tr["max_blocks"])

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    params = jax.tree.map(lambda p: shape(p.shape, BF16), model_spec(cfg),
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    cache = jax.tree.map(lambda s: shape(s.shape, s.dtype),
                         cache_spec(cfg, layout, B))
    vec = shape((B,), jnp.int32)
    dec = jax.jit(lambda p, c, t, n, tb, a: decode_step(
        p, cfg, c, t, n, tb, layout, active=a, attn_impl="gather")).lower(
        params, cache, vec, vec, shape((B, layout.max_blocks), jnp.int32),
        shape((B,), bool)).compile()
    S = max(traffic_mod.prompt_shapes(tr))
    S = -(-S // bt) * bt
    pre = jax.jit(lambda p, c, t, tb, last: prefill_step(
        p, cfg, c, t, tb, layout, chunk=256, last_index=last)).lower(
        params, cache, shape((1, S), jnp.int32),
        shape((1, layout.max_blocks), jnp.int32),
        shape((1,), jnp.int32)).compile()
    leaves = jax.tree.leaves
    return {"pool_blocks": tr["pool_blocks"],
            "max_blocks": tr["max_blocks"],
            "weight_bytes": sum(x.size * 2 for x in leaves(params)),
            "pool_bytes": sum(x.size * x.dtype.itemsize
                              for x in leaves(cache)),
            "decode": analyse(dec), f"prefill_{S}": analyse(pre)}


def main() -> None:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = set(sys.argv[1:])
    for cell in bench["workloads"]:
        if want and cell["name"] not in want:
            continue
        print(json.dumps({"cell": cell["name"],
                          **cell_sizes(cell, one_chip)}), flush=True)


if __name__ == "__main__":
    main()
