"""OffloadEngine integration tests: numerics vs the reference decode path,
precision-substitution effects, stats coherence, and the paged-KV model
class the engine serves."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import EngineConfig, OffloadEngine, Thresholds
from repro.models import build_model
from repro.models.model import supports_paged_kv


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=4, d_model=128,
                        vocab=256)
    cfg = dataclasses.replace(cfg, dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return m, params


def _reference_nll(m, params, toks):
    cache = m.init_cache(1, len(toks) + 1)
    pos = jnp.zeros((1,), jnp.int32)
    nll, n = 0.0, 0
    lg, cache = m.decode_step(params, cache, jnp.asarray([[toks[0]]]), pos)
    for t in toks[1:]:
        p = np.asarray(lg[0], np.float64)
        p -= p.max()
        p -= np.log(np.exp(p).sum())
        nll -= p[t]
        n += 1
        pos = pos + 1
        lg, cache = m.decode_step(params, cache, jnp.asarray([[t]]), pos)
    return nll / n


def test_all_hi_engine_matches_reference_exactly(setup):
    m, params = setup
    toks = [1, 5, 9, 13, 2, 7, 20, 33]
    eng = OffloadEngine(m, params, EngineConfig(
        hi_slots=32, lo_slots=1, thresholds=Thresholds(1.0, 1.0),
        prefetch=False))
    got = eng.score_nll(toks)
    want = _reference_nll(m, params, toks)
    assert abs(got - want) < 1e-4


def test_mixed_precision_close_but_not_identical(setup):
    m, params = setup
    toks = [1, 5, 9, 13, 2, 7, 20, 33, 40, 41]
    base = OffloadEngine(m, params, EngineConfig(
        hi_slots=32, lo_slots=1, thresholds=Thresholds(1.0, 1.0), prefetch=False))
    mixed = OffloadEngine(m, params, EngineConfig(
        hi_slots=32, lo_slots=32, thresholds=Thresholds(0.55, 1.0),
        prefetch=False))
    nb, nm = base.score_nll(toks), mixed.score_nll(toks)
    assert nm != nb                       # int4 substitution changes numerics
    assert abs(nm - nb) / nb < 0.15       # ... but only slightly
    assert mixed.loader.n_loads[1] > 0    # some lo-precision loads happened


@pytest.mark.xfail(strict=False,
                   reason="statistical property of trained routers; on "
                          "random-init smoke models the ordering is a coin "
                          "flip (failed at seed too)")
def test_skip_degrades_more_than_replace(setup):
    m, params = setup
    toks = list(range(1, 24))
    base = OffloadEngine(m, params, EngineConfig(
        hi_slots=32, lo_slots=4, thresholds=Thresholds(1.0, 1.0), prefetch=False))
    rep = OffloadEngine(m, params, EngineConfig(
        hi_slots=32, lo_slots=32, thresholds=Thresholds(0.5, 1.0), prefetch=False))
    skp = OffloadEngine(m, params, EngineConfig(
        hi_slots=32, lo_slots=4, thresholds=Thresholds(0.5, 0.5), prefetch=False))
    nb = base.score_nll(toks)
    assert abs(rep.score_nll(toks) - nb) <= abs(skp.score_nll(toks) - nb) + 1e-6


def test_engine_stats_consistent(setup):
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=8, lo_slots=4))
    eng.generate([1, 2, 3], 6)
    s = eng.stats()
    cs = s["cache"]
    assert cs["hits"] + cs["misses"] > 0
    assert cs["hit_ratio"] == pytest.approx(
        cs["hits"] / (cs["hits"] + cs["misses"]))
    assert s["loads_hi"] + s["loads_lo"] > 0
    assert s["loaded_bytes"] > 0
    # every trace token covers every MoE layer
    assert all(len(tok) == eng.num_moe_layers for tok in eng.trace)
    # the whole stats dict round-trips through JSON (serving API contract)
    import json
    assert json.loads(json.dumps(s))["cache"]["hits"] == cs["hits"]
    for key in ("load_stall_s", "overlap_fraction", "gating_s"):
        assert s[key] >= 0.0


def test_engine_small_cache_thrashes_but_stays_correct(setup):
    m, params = setup
    toks = [1, 5, 9, 13]
    tiny = OffloadEngine(m, params, EngineConfig(
        hi_slots=2, lo_slots=1, thresholds=Thresholds(1.0, 1.0), prefetch=False))
    want = _reference_nll(m, params, toks)
    assert abs(tiny.score_nll(toks) - want) < 1e-4
    assert tiny.cache.stats.hit_ratio() < 0.6   # lots of misses with 2 slots


def test_engine_refuses_model_without_paged_kv():
    """The engine serves paged KV only: a MoE model whose attention the page
    pool cannot hold (DeepSeek-V2's latent MLA cache) is refused when the
    engine is built, not at its first start_batch."""
    cfg = smoke_variant(get_config("deepseek-v2-236b"))
    m = build_model(cfg)
    assert cfg.moe is not None and not supports_paged_kv(cfg)
    with pytest.raises(ValueError, match="paged KV"):
        OffloadEngine(m, m.init(jax.random.PRNGKey(0)), EngineConfig())
