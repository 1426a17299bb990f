"""A cell's files, the device check, and the engine a configuration
describes.  Shared by ``run.py`` and the calibration scripts."""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass, replace
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class CellError(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    entry: dict                  # the BENCHMARK.json workload
    bench: dict
    cfile: dict                  # configs/<config>.json
    traffic: dict                # traffic/<traffic>.json
    cfg: object = None           # the served ModelConfig
    ref_cfg: dict | None = None  # sizes the reference reads
    device: object = None
    peaks: dict | None = None

    @property
    def engine_cfg(self) -> dict:
        return self.cfile["engine"]


def open_cell(name: str, smoke: bool = False) -> Cell:
    """Load the cell's files, start JAX with the checkout's compile cache,
    and check the device: a TPU (unless ``smoke``) with the cell's chips
    and a row in ``peaks.json``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    cfile = json.loads((HERE / "configs" / f"{entry['config']}.json")
                       .read_text())
    import traffic as traffic_mod
    c = Cell(name, entry, bench, cfile, traffic_mod.load(entry["traffic"]))
    # the program and the reference attend over the whole context: a
    # traffic that outgrows a stated window would serve another model
    window = cfile.get("sliding_window")
    if window and traffic_mod.max_context(c.traffic) > window:
        raise CellError(f"traffic {entry['traffic']} reaches contexts of "
                        f"{traffic_mod.max_context(c.traffic)} tokens, past "
                        f"the configuration's {window}-token window")

    # one fixed compile-cache directory inside the checkout, unless the
    # environment names one
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".cache" / "xla"))
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: a cap that holds one cell's programs would make every
    # run after another cell's compile again
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    c.device = devs[0]
    if not smoke and c.device.platform != "tpu":
        raise CellError(f"needs a TPU; JAX found {c.device.platform!r}")
    if len(devs) < entry["chips"]:
        raise CellError(f"cell asks for {entry['chips']} chips; JAX found "
                        f"{len(devs)}")
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    c.peaks = peaks.get(c.device.device_kind)
    if c.device.platform == "tpu" and c.peaks is None:
        raise CellError(f"no peaks for device kind "
                        f"{c.device.device_kind!r} in peaks.json")

    from repro.configs.base import get_config, get_smoke_config
    if smoke:
        cfg = get_smoke_config(cfile["arch"])
        c.ref_cfg = {"num_attention_heads": cfg.n_heads,
                     "num_key_value_heads": cfg.kv_heads,
                     "num_hidden_layers": cfg.n_layers,
                     "rms_norm_eps": cfile["rms_norm_eps"],
                     "rope_theta": cfg.attn.rope_theta,
                     "vocab_size": cfg.vocab}
    else:
        cfg = replace(get_config(cfile["arch"]),
                      n_layers=cfile["num_hidden_layers"])
        c.ref_cfg = cfile
        want = (cfile["hidden_size"], cfile["intermediate_size"],
                cfile["num_attention_heads"], cfile["num_key_value_heads"],
                cfile["vocab_size"], cfile["rope_theta"])
        got = (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.kv_heads, cfg.vocab,
               cfg.attn.rope_theta)
        if want != got:
            raise CellError(f"program config {cfile['arch']} {got} is not "
                            f"the file's {want}")
    c.cfg = cfg
    return c


def build_engine(c: Cell, params, seed: int):
    """The ServingEngine the configuration and traffic files describe."""
    import traffic as traffic_mod
    import weights
    from repro.launch.serve import default_profile
    from repro.models.decode import PagedLayout
    from repro.serving import ServingEngine

    eng, tr = c.engine_cfg, c.traffic
    MB = tr["max_blocks"]
    layout = PagedLayout(num_blocks=tr["pool_blocks"],
                         block_tokens=eng["block_tokens"], max_blocks=MB)
    if traffic_mod.max_context(tr) > MB * layout.block_tokens:
        raise CellError("traffic's longest context exceeds max_blocks")
    kw = {}
    if eng.get("prefix_cache"):
        kw = dict(prefix_cache=int(tr["prefix_cache_blocks"]),
                  evict_policy=eng["evict_policy"])
    profile = default_profile(MB) if eng["policy"] == "ebpf" else None
    return ServingEngine(c.cfg, params, layout, max_batch=eng["max_batch"],
                         policy=eng["policy"], profile=profile,
                         seed=weights.jax_seed(seed), **kw)
