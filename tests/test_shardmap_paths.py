"""Multi-device equivalence of the distributed (shard_map) execution paths
against single-device references, on 8 fake CPU devices in a subprocess
(device count must be set before jax initializes — hence the isolation).

Covers the §Perf hillclimb code paths:
  * paged_decode_attention_sharded (GQA flash-decoding, batch-sharded)
  * paged_mla_decode_sharded       (MLA latent flash-decoding)
  * moe_apply_ep                   (expert-parallel all-to-all dispatch)
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)

# ---------------- GQA flash-decoding vs gather reference -----------------
from repro.distributed.flashdecode import (set_decode_mesh,
                                           paged_decode_attention_sharded)
from repro.models.decode import paged_decode_attention_gather

set_decode_mesh(mesh)
B, H, KVH, hd, bt = 4, 8, 4, 16, 4
NB, MB = 64, 8          # NB divisible by 8 shards; MB by model=4
q = jnp.asarray(rng.normal(size=(B, H, hd)).astype(np.float32))
pk = jnp.asarray(rng.normal(size=(NB, bt, KVH, hd)).astype(np.float32))
pv = jnp.asarray(rng.normal(size=(NB, bt, KVH, hd)).astype(np.float32))
lengths = jnp.asarray([9, 17, 25, 32], jnp.int32)

# blocks for sequence b (data shard d = b // 2) must live in shard rows:
# shard (d, m) owns rows [ (d*4+m)*8, +8 ). Round-robin logical blocks over m.
NB_loc = NB // 8
tbl = np.full((B, MB), -1, np.int32)
sh_tbl = np.full((B, 4, MB // 4), -1, np.int32)
sh_log = np.full((B, 4, MB // 4), -1, np.int32)
counters = {}
for b in range(B):
    d = b // 2
    nblk = int(np.ceil(float(lengths[b]) / bt))
    for lb in range(nblk):
        m = lb % 4
        shard = d * 4 + m
        slot = counters.get((shard, b), 0)
        counters[(shard, b)] = slot + 1
        phys = shard * NB_loc + b % 2 + slot * 2     # unique row in shard
        tbl[b, lb] = phys
        sh_tbl[b, m, lb // 4] = phys
        sh_log[b, m, lb // 4] = lb
tbl, sh_tbl, sh_log = map(jnp.asarray, (tbl, sh_tbl, sh_log))

ref_out, ref_heat = paged_decode_attention_gather(
    q, pk, pv, tbl, lengths, block_tokens=bt)
out, heat = jax.jit(lambda *a: paged_decode_attention_sharded(
    *a, block_tokens=bt))(q, pk, pv, sh_tbl, sh_log, lengths)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                           rtol=2e-5, atol=2e-5)
# heat is normalized per shard (running-max semantics, like the Pallas
# kernel) so only structural invariants hold vs the exact reference
h = np.asarray(heat)
assert np.isfinite(h).all() and (h >= 0).all()
assert (h.sum(-1) > 0).all()
print("GQA flashdecode OK")

# ---------------- MLA latent flash-decoding ------------------------------
from repro.distributed.flashdecode import paged_mla_decode_sharded
from repro.models.decode import paged_decode_attention_mla_gather

L, Dr, Dn = 32, 8, 16
pool = jnp.asarray(rng.normal(size=(NB, bt, L + Dr)).astype(np.float32))
q_eff = jnp.asarray(rng.normal(size=(B, H, L)).astype(np.float32))
q_rope = jnp.asarray(rng.normal(size=(B, H, Dr)).astype(np.float32))
r_lat, r_heat = paged_decode_attention_mla_gather(
    q_eff, q_rope, pool, tbl, lengths, block_tokens=bt, kv_lora=L,
    qk_nope=Dn)
o_lat, m_heat = jax.jit(lambda *a: paged_mla_decode_sharded(
    *a, block_tokens=bt, kv_lora=L, qk_nope=Dn))(
        q_eff, q_rope, pool, sh_tbl, sh_log, lengths)
np.testing.assert_allclose(np.asarray(o_lat), np.asarray(r_lat),
                           rtol=2e-5, atol=2e-5)
mh = np.asarray(m_heat)
assert np.isfinite(mh).all() and (mh >= 0).all() and (mh.sum(-1) > 0).all()
print("MLA flashdecode OK")

# ---------------- EP MoE vs local dispatch -------------------------------
from repro.configs.base import MoECfg
from repro.models.moe import moe_apply_ep, _moe_apply_local, moe_spec
from repro.models.common import materialize

cfg = MoECfg(num_experts=8, top_k=2, d_ff_expert=16, num_shared=1,
             capacity_factor=8.0)
spec = moe_spec(32, cfg, "swiglu")
params = materialize(jax.random.PRNGKey(1), spec)
x = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
ref, ref_aux = _moe_apply_local(params, x, cfg, "swiglu")
out, aux = moe_apply_ep(params, x, cfg, "swiglu", mesh)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=1e-4, atol=1e-4)
print("EP MoE OK; aux local/ep:", float(ref_aux), float(aux))
"""


@pytest.mark.timeout(600)
def test_shardmap_paths_match_references():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(root / "src"))
    r = subprocess.run([sys.executable, "-c", CODE], cwd=root,
                       env=env, capture_output=True, text=True, timeout=580)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "GQA flashdecode OK" in r.stdout
    assert "MLA flashdecode OK" in r.stdout
    assert "EP MoE OK" in r.stdout
