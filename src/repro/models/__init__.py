"""Pure-JAX model zoo: one generic assembly (transformer.py) configured per
architecture, plus the serving-side paged-KV execution paths (decode.py)."""

from .common import abstract, logical_axes, materialize, pad_vocab, param_count
from .decode import (PagedLayout, cache_init, cache_spec, decode_step,
                     prefill_step)
from .transformer import (build_layer_plans, build_segments, lm_loss,
                          model_forward, model_spec, serving_params)

__all__ = [
    "abstract", "logical_axes", "materialize", "pad_vocab", "param_count",
    "PagedLayout", "cache_init", "cache_spec", "decode_step", "prefill_step",
    "build_layer_plans", "build_segments", "lm_loss", "model_forward",
    "model_spec", "serving_params",
]
