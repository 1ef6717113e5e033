"""ElasticFamily protocol: transformer/SSM mask algebra (masked parent ==
extracted submodel, property-tested over random specs), batched-vs-
sequential A/B for a transformer zoo config, cohort-axis sharding, the
genes()-keyed spec-table cache, and a per-family one-round smoke."""
import dataclasses
import json
import os
import random
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS, reduced
from repro.configs.paper_cnn import CNNConfig
from repro.core import (SubmodelSpec, TransformerSubSpec,
                        extract_transformer, family_for, full_spec)
from repro.data import make_dataset, make_lm_dataset
from repro.fl.engine import BatchedRoundEngine, SequentialFamilyTrainer
from repro.models import cnn
from repro.models import transformer as T

DENSE = reduced(ARCHS["granite-3-8b"], n_layers=4, d_model=64)
SSMCFG = reduced(ARCHS["mamba2-2.7b"], n_layers=3, d_model=64)
CNN_CFG = CNNConfig(name="fam-test", in_channels=1, image_size=28,
                    stem_channels=8, stages=((16, 2), (32, 2)),
                    groupnorm_groups=4, elastic_widths=(0.5, 1.0))

_PARAMS = {}


def _params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = T.init_params(jax.random.PRNGKey(0), cfg)
    return _PARAMS[cfg.name]


def _check_masked_equals_extracted(cfg, spec, atol=1e-5):
    fam = family_for(cfg)
    params = _params(cfg)
    x = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, cfg.vocab_size)
    sub, sub_cfg = extract_transformer(params, cfg, spec)
    ref, _ = T.forward(sub, sub_cfg, {"tokens": x})
    masks = jax.tree.map(jnp.asarray, fam.spec_masks(spec).fwd)
    got, _ = T.forward(params, cfg, {"tokens": x}, masks=masks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=atol)


def _layers_from_bitmask(n, bits):
    keep = tuple(i for i in range(n) if bits & (1 << i))
    return keep if keep else (0,)


# ---------------------------------------------------------------------------
# property tests: masked parent-space forward == extracted-submodel forward
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(bits=st.integers(1, 15),
       ff=st.sampled_from([0.25, 0.5, 0.75, 1.0]))
def test_dense_masked_forward_matches_extracted(bits, ff):
    spec = TransformerSubSpec(layers=(_layers_from_bitmask(4, bits),),
                              ff_frac=ff)
    _check_masked_equals_extracted(DENSE, spec)


@settings(max_examples=8, deadline=None)
@given(bits=st.integers(1, 7),
       heads=st.sampled_from([0.25, 0.5, 0.75, 1.0]))
def test_ssm_masked_forward_matches_extracted(bits, heads):
    spec = TransformerSubSpec(layers=(_layers_from_bitmask(3, bits),),
                              ssm_head_frac=heads)
    _check_masked_equals_extracted(SSMCFG, spec)


HEADS = dataclasses.replace(reduced(ARCHS["granite-3-8b"], n_layers=2,
                                    d_model=64),
                            name="attn-heads-test", n_heads=8, n_kv_heads=4,
                            head_dim=8)


@settings(max_examples=8, deadline=None)
@given(bits=st.integers(1, 3),
       heads=st.sampled_from([0.25, 0.5, 0.75, 1.0]))
def test_attn_heads_masked_forward_matches_extracted(bits, heads):
    """GQA head-prefix masking == the sliced submodel (whole query groups:
    kept KV heads keep their full groups, so the q→kv mapping agrees)."""
    spec = TransformerSubSpec(layers=(_layers_from_bitmask(2, bits),),
                              attn_head_frac=heads)
    _check_masked_equals_extracted(HEADS, spec)


def test_moe_masked_forward_matches_extracted():
    """Expert-width masking: exact vs the sliced submodel when neither
    path drops tokens (capacity_factor high enough to hold every token —
    parent and submodel size their capacity buffers from different expert
    counts, so token drops are the one place the two paths may diverge)."""
    cfg = reduced(ARCHS["granite-moe-1b-a400m"], n_layers=2, d_model=64)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    for spec in [TransformerSubSpec(layers=((0, 1),), expert_frac=0.5),
                 TransformerSubSpec(layers=((1,),), ff_frac=0.5,
                                    expert_frac=0.5)]:
        _check_masked_equals_extracted(cfg, spec)


def test_hybrid_masked_forward_matches_extracted():
    """zamba2-style hybrid: ssm segments + shared attention block. The
    shared block is kept whole by every submodel — width masks must not
    leak into it."""
    cfg = reduced(ARCHS["zamba2-1.2b"], n_layers=3, d_model=64)
    for spec in [TransformerSubSpec(layers=((0,), (1,)), ssm_head_frac=0.5),
                 TransformerSubSpec(layers=((0,), (0, 1)), ff_frac=0.5)]:
        _check_masked_equals_extracted(cfg, spec)


# ---------------------------------------------------------------------------
# spec-table cache (genes-keyed LRU)
# ---------------------------------------------------------------------------
def test_spec_masks_cached_by_genes():
    fam = family_for(DENSE)
    a = TransformerSubSpec(layers=((0, 2),), ff_frac=0.5)
    b = TransformerSubSpec(layers=((0, 2),), ff_frac=0.5)
    assert fam.genes(a) == fam.genes(b)
    assert fam.spec_masks(a) is fam.spec_masks(b)      # no rebuild
    c = TransformerSubSpec(layers=((0, 2),), ff_frac=0.75)
    assert fam.spec_masks(c) is not fam.spec_masks(a)
    # CNN family shares the same spec-table discipline
    cf = family_for(CNN_CFG)
    s = SubmodelSpec((1, 2), (0.5, 1.0))
    assert cf.spec_masks(s) is cf.spec_masks(SubmodelSpec((1, 2), (0.5, 1.0)))


def test_engine_cohort_masks_cache_hits_across_rounds():
    """Identical spec mixes (by genes) must reuse the stacked CohortMasks
    — spec churn with repeats stops rebuilding identical pytrees."""
    eng = BatchedRoundEngine(CNN_CFG, lr=0.05, momentum=0.9)
    specs = [full_spec(CNN_CFG), SubmodelSpec((1, 2), (0.5, 1.0))]
    m1 = eng._cohort_masks(specs)
    m2 = eng._cohort_masks([full_spec(CNN_CFG),
                            SubmodelSpec((1, 2), (0.5, 1.0))])
    assert m1 is m2


# ---------------------------------------------------------------------------
# batched == sequential A/B for a transformer zoo config
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_transformer_batched_round_matches_sequential():
    """One CFL round over a depth+width-heterogeneous transformer cohort:
    parent params within 1e-5, per-client accuracies within 1e-3."""
    cfg = reduced(ARCHS["granite-3-8b"], n_layers=2, d_model=64)
    fam = family_for(cfg)
    specs = [fam.full_spec(),
             TransformerSubSpec(layers=((0,),), ff_frac=0.5),
             TransformerSubSpec(layers=((1,),), ff_frac=0.25)]
    K = len(specs)
    datasets = [make_lm_dataset(40, 16, cfg.vocab_size, seed=k)
                for k in range(K)]
    tdata = [make_lm_dataset(16, 16, cfg.vocab_size, seed=100 + k)
             for k in range(K)]
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    sizes = [float(len(d["y"])) for d in datasets]
    kw = dict(batch_size=8, epochs=1, seeds=[7, 8, 9])
    eng = BatchedRoundEngine(cfg, lr=0.05, momentum=0.9)
    pb, accs_b, nb = eng.run_fl_round(params, specs, datasets, tdata,
                                      sizes, **kw)
    seq = SequentialFamilyTrainer(cfg, lr=0.05, momentum=0.9)
    ps, accs_s, ns = seq.run_fl_round(params, specs, datasets, tdata,
                                      sizes, **kw)
    assert list(nb) == list(ns)
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), pb, ps)
    assert max(jax.tree.leaves(err)) < 1e-5
    np.testing.assert_allclose(accs_b, accs_s, atol=1e-3)


# ---------------------------------------------------------------------------
# per-family one-round smoke (fails fast on engine regressions)
# ---------------------------------------------------------------------------
def test_batched_round_smoke_cnn_family():
    params = cnn.init_params(jax.random.PRNGKey(0), CNN_CFG)
    data = make_dataset("synthmnist", 160, seed=5)
    datasets = [{k: v[i * 60:(i + 1) * 60] for k, v in data.items()}
                for i in range(2)]
    tdata = [{k: v[120 + i * 20:120 + (i + 1) * 20] for k, v in data.items()}
             for i in range(2)]
    specs = [full_spec(CNN_CFG), SubmodelSpec((1, 1), (0.5, 0.5))]
    eng = BatchedRoundEngine(CNN_CFG, lr=0.05, momentum=0.9)
    new_p, accs, n_steps = eng.run_fl_round(
        params, specs, datasets, tdata, [60.0, 60.0],
        batch_size=32, epochs=1, seeds=[1, 2])
    assert all(np.isfinite(v).all() for v in jax.tree.leaves(new_p))
    assert len(accs) == 2 and all(0.0 <= a <= 1.0 for a in accs)


def test_batched_round_smoke_transformer_family():
    cfg = reduced(ARCHS["granite-3-8b"], n_layers=2, d_model=64)
    fam = family_for(cfg)
    specs = [fam.full_spec(), TransformerSubSpec(layers=((0,),), ff_frac=0.5)]
    datasets = [make_lm_dataset(24, 12, cfg.vocab_size, seed=k)
                for k in range(2)]
    tdata = [make_lm_dataset(8, 12, cfg.vocab_size, seed=50 + k)
             for k in range(2)]
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    eng = BatchedRoundEngine(cfg, lr=0.05, momentum=0.9)
    new_p, accs, n_steps = eng.run_fl_round(
        params, specs, datasets, tdata, [24.0, 24.0],
        batch_size=8, epochs=1, seeds=[3, 4])
    assert all(np.isfinite(v).all() for v in jax.tree.leaves(new_p))
    assert len(accs) == 2 and all(0.0 <= a <= 1.0 for a in accs)


# ---------------------------------------------------------------------------
# cohort-axis sharding
# ---------------------------------------------------------------------------
def test_cohort_sharded_engine_annotates_and_matches_unsharded():
    """cohort_shards engages the sharding path (mesh + device_put with a
    PartitionSpec('cohort') layout) and leaves round math unchanged. On a
    single-device CPU the mesh clamps to 1 shard; the 2-device case runs
    in the subprocess test below."""
    from repro.sharding import effective_cohort_shards
    assert effective_cohort_shards(4, 2, n_devices=2) == 2
    assert effective_cohort_shards(5, 2, n_devices=2) == 1
    assert effective_cohort_shards(6, 4, n_devices=8) == 3
    params = cnn.init_params(jax.random.PRNGKey(0), CNN_CFG)
    data = make_dataset("synthmnist", 160, seed=6)
    datasets = [{k: v[i * 60:(i + 1) * 60] for k, v in data.items()}
                for i in range(2)]
    tdata = [{k: v[120 + i * 20:120 + (i + 1) * 20] for k, v in data.items()}
             for i in range(2)]
    specs = [full_spec(CNN_CFG), SubmodelSpec((2, 1), (1.0, 0.5))]
    kw = dict(batch_size=32, epochs=1, seeds=[1, 2])
    e1 = BatchedRoundEngine(CNN_CFG, lr=0.05, momentum=0.9)
    p1, a1, _ = e1.run_fl_round(params, specs, datasets, tdata,
                                [60.0, 60.0], **kw)
    e2 = BatchedRoundEngine(CNN_CFG, lr=0.05, momentum=0.9, cohort_shards=2)
    assert e2.cohort_sharding(2) is not None
    p2, a2, _ = e2.run_fl_round(params, specs, datasets, tdata,
                                [60.0, 60.0], **kw)
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)
    assert max(jax.tree.leaves(err)) < 1e-5
    np.testing.assert_allclose(a1, a2, atol=1e-5)


_SHARD_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, r"%s")
import json
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.paper_cnn import CNNConfig
from repro.core import SubmodelSpec, full_spec, minimal_spec
from repro.data import make_dataset
from repro.fl.engine import BatchedRoundEngine
from repro.models import cnn

CFG = CNNConfig(name="shard-sub", in_channels=1, image_size=28,
                stem_channels=8, stages=((16, 2), (32, 2)),
                groupnorm_groups=4, elastic_widths=(0.5, 1.0))
params = cnn.init_params(jax.random.PRNGKey(0), CFG)
data = make_dataset("synthmnist", 280, seed=1)
datasets = [{k: v[i*60:(i+1)*60] for k, v in data.items()} for i in range(4)]
tdata = [{k: v[240+i*10:240+(i+1)*10] for k, v in data.items()}
         for i in range(4)]
specs = [full_spec(CFG), minimal_spec(CFG),
         SubmodelSpec((1, 2), (0.5, 1.0)), SubmodelSpec((2, 1), (1.0, 0.5))]
kw = dict(batch_size=32, epochs=1, seeds=[1, 2, 3, 4])
e1 = BatchedRoundEngine(CFG, lr=0.05, momentum=0.9)
p1, a1, _ = e1.run_fl_round(params, specs, datasets, tdata, [60.0]*4, **kw)
e2 = BatchedRoundEngine(CFG, lr=0.05, momentum=0.9, cohort_shards=2)
sh = e2.cohort_sharding(4)
assert sh is not None and sh.mesh.shape["cohort"] == 2, sh
p2, a2, _ = e2.run_fl_round(params, specs, datasets, tdata, [60.0]*4, **kw)
err = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)))
print(json.dumps({"err": err, "accs_match":
                  bool(np.allclose(a1, a2, atol=1e-5)), "shards": 2}))
"""


@pytest.mark.slow
def test_cohort_sharding_two_fake_devices():
    """2-device CPU mesh in a subprocess: a 2-way cohort-sharded round is
    numerically identical to the unsharded one."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SHARD_SUB % src],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] < 1e-5, rec
    assert rec["accs_match"], rec
