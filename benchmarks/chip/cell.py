"""A cell's files, the device check, and the engine a configuration
describes.  Shared by ``run.py`` and the calibration scripts.

A configuration file (``configs/<name>.json``) states the model in the
keys of its published ``config.json`` and says what runs: ``arch`` names
the program's registry entry, and ``program`` overrides fields of that
``ModelConfig`` by dotted path (``{"n_layers": 15}``, ``{"moe.top_k": 6}``),
the cut to one chip's share.  ``served_config`` applies the overrides and
then holds every published key that ``HF_KEYS`` knows to the config that
is served: a key the file names under ``assumed`` is a departure stated
there and is not held.  A key under ``reduced`` differs from its
``published`` value.  At smoke size the registry's smoke model runs as it
is, and ``ref_sizes`` gives the reference that model's sizes under the
same keys.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass, fields, replace
from pathlib import Path

import workcount

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# published config.json key -> where the program's ModelConfig holds it: a
# dotted field path, or a function of the config.  Families that name one
# size differently map their names to the same field.
HF_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "attn.rope_theta",
    "sliding_window": "attn.window",
    "attn_logit_softcapping": "attn.logit_soft_cap",
    # sparse experts; a file states the routed experts held here, the
    # chip's share (the router keeps the published count)
    "n_routed_experts": lambda c: c.moe and workcount.experts_held(c),
    "num_experts": lambda c: c.moe and workcount.experts_held(c),
    "num_local_experts": lambda c: c.moe and workcount.experts_held(c),
    "num_experts_per_tok": "moe.top_k",
    "moe_intermediate_size": "moe.d_ff_expert",
    "n_shared_experts": "moe.num_shared",
    "first_k_dense_replace": "moe.first_dense",
    "moe_layer_freq": "moe.every",
    "expert_layer_offset": "moe.first_dense",
    "expert_layer_period": "moe.every",
    # latent attention
    "kv_lora_rank": "mla.kv_lora",
    "qk_nope_head_dim": "mla.qk_nope",
    "qk_rope_head_dim": "mla.qk_rope",
    "v_head_dim": "mla.v_head",
    # state-space layers and their interleave with attention
    "mamba_d_state": "mamba.d_state",
    "ssm_state_size": "mamba.d_state",
    "mamba_d_conv": "mamba.conv_dim",
    "conv_kernel": "mamba.conv_dim",
    "mamba_expand": "mamba.expand",
    "expand": "mamba.expand",
    "mamba_head_dim": "mamba.head_dim",
    "mamba_chunk_size": "mamba.chunk",
    "chunk_size": "mamba.chunk",
    "attn_layer_period": lambda c: (len(c.hybrid_pattern)
                                    if c.hybrid_pattern else None),
    "attn_layer_offset": lambda c: (c.hybrid_pattern.index("a")
                                    if c.hybrid_pattern else None),
}


class CellError(RuntimeError):
    pass


def _program_on_path() -> None:
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def hf_value(cfg, key: str):
    """The value of published key ``key`` in a ModelConfig (None where the
    config has no such part)."""
    where = HF_KEYS[key]
    if callable(where):
        return where(cfg)
    for name in where.split("."):
        if cfg is None:
            return None
        cfg = getattr(cfg, name)
    return cfg


def _override(obj, path: str, value):
    """``obj`` with the field at dotted ``path`` set to ``value``."""
    head, _, rest = path.partition(".")
    if head not in {f.name for f in fields(obj)}:
        raise CellError(f"program override {path!r}: "
                        f"{type(obj).__name__} has no field {head!r}")
    old = getattr(obj, head)
    if rest:
        if old is None:
            raise CellError(f"program override {path!r}: the config has no "
                            f"{head}")
        value = _override(old, rest, value)
    elif isinstance(old, tuple) and isinstance(value, list):
        value = tuple(value)
    return replace(obj, **{head: value})


def served_config(cfile: dict):
    """The ModelConfig a configuration file serves at full size: the
    registry's ``arch`` with the file's ``program`` overrides, checked
    against every published key of the file that ``HF_KEYS`` knows."""
    _program_on_path()
    from repro.configs.base import get_config
    cfg = get_config(cfile["arch"])
    for path, value in cfile.get("program", {}).items():
        cfg = _override(cfg, path, value)
    published = cfile.get("published", {})
    for key in cfile.get("reduced", []):
        if key not in published or published[key] == cfile.get(key):
            raise CellError(f"reduced key {key!r} does not differ from its "
                            f"published value")
    assumed = cfile.get("assumed", {})
    wrong = {k: (cfile[k], hf_value(cfg, k)) for k in HF_KEYS
             if k in cfile and k not in assumed
             and hf_value(cfg, k) != cfile[k]}
    if wrong:
        raise CellError(f"program config {cfile['arch']} serves another "
                        f"model than the file states (key: file, served): "
                        f"{wrong}")
    return cfg


def ref_sizes(cfg, cfile: dict) -> dict:
    """What the reference reads for config ``cfg``: the file's keys that
    are not sizes (``rms_norm_eps``), and every ``HF_KEYS`` size of
    ``cfg``."""
    out = {k: v for k, v in cfile.items() if k not in HF_KEYS}
    for key in HF_KEYS:
        v = hf_value(cfg, key)
        if v is not None:
            out[key] = v
    return out


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
    _program_on_path()
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

    if smoke:
        from repro.configs.base import get_smoke_config
        c.cfg = get_smoke_config(cfile["arch"])
        c.ref_cfg = ref_sizes(c.cfg, cfile)
    else:
        c.cfg = served_config(cfile)
        c.ref_cfg = cfile
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
