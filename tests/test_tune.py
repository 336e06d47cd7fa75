"""Acceptance suite for ``repro.tune`` — search-based plan autotuning.

Pins the contracts ISSUE 9 states:

  * the candidate space is budget-feasible BY CONSTRUCTION — every
    enumerated plan fits the VMEM budget (so every tuned plan satisfies
    ``EngineConfig(strict_vmem=True)``), and the first-fit heuristic's
    plan is a point of that same space (ONE enumeration, ONE byte model);
  * the tuner is deterministic for a fixed seed (model-only mode);
  * a measured winner is never slower than the first-fit heuristic —
    the heuristic is always in the measured pool, so min() guarantees it;
  * the ``TunedPlanCache`` round-trips through JSON losslessly, rejects
    plans that overflow the CALLER's budget at lookup, and invalidates
    (silently, or loudly under ``strict=True``) on a schema-version bump;
  * ``UniformEngine.plan`` consults ``EngineConfig(tuned_plans=...)``
    before the heuristic, and telemetry distinguishes ``tuned_hit`` from
    heuristic fallback (``engine_plan_tuned_hits_total`` vs
    ``engine_plan_heuristic_total``), with ``plan_sources`` as the
    telemetry-free mirror;
  * a SECOND engine built from the persisted file replans a whole network
    with zero search and zero heuristic work, at XLA parity.
"""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs, tune
from repro.core import (
    EngineConfig,
    UniformEngine,
    compile_network,
    init_network_weights,
    networks,
)
from repro.core import tiling
from repro.tune.cache import TunedEntry

GEOM = tune.LayerGeometry(mode="deconv", in_spatial=(4, 1, 4),
                          kernel=(3, 1, 3), stride=(2, 1, 2),
                          cin=8, cout=4)
GEOM3 = tune.LayerGeometry(mode="deconv", in_spatial=(4, 4, 4),
                           kernel=(3, 3, 3), stride=(2, 2, 2),
                           cin=8, cout=8)


def _chain():
    return networks.deconv_stack("t", 2, 4, [8, 4, 3])


# ---------------------------------------------------------------------------
# Candidate space: ONE enumeration, feasible by construction
# ---------------------------------------------------------------------------

class TestCandidateSpace:
    def test_every_candidate_fits_budget(self):
        budget = 1024 * 1024
        cands = tune.candidate_plans(GEOM3, vmem_budget=budget)
        assert cands
        for p in cands:
            assert p.step_vmem_bytes <= budget
            assert not p.overflows

    def test_heuristic_is_a_point_of_the_space(self):
        heur = tiling.plan_uniform_tiles(
            GEOM.in_spatial, GEOM.kernel, GEOM.stride, GEOM.cin, GEOM.cout)
        cands = tune.candidate_plans(GEOM)
        assert heur in cands          # modeled_cost is compare=False

    def test_candidates_carry_modeled_cost(self):
        for p in tune.candidate_plans(GEOM):
            assert p.modeled_cost > 0.0

    def test_strict_vmem_engine_accepts_every_candidate(self):
        """Any tuned winner passes EngineConfig(strict_vmem=True)."""
        budget = 1024 * 1024
        for p in tune.candidate_plans(GEOM3, vmem_budget=budget):
            cache = tune.TunedPlanCache()
            cache.put(GEOM3.key_tuple, p)
            eng = UniformEngine(EngineConfig(
                method="pallas", max_tile_bytes=budget, strict_vmem=True,
                tuned_plans=cache))
            got = eng.plan(GEOM3.mode, GEOM3.in_spatial, GEOM3.kernel,
                           GEOM3.stride, GEOM3.cin, GEOM3.cout)
            assert got == p

    def test_overflow_geometry_falls_back_to_heuristic_plan(self):
        """A budget below the smallest feasible point still returns the
        planner's best-effort overflow plan (never an empty space)."""
        cands = tune.candidate_plans(GEOM3, vmem_budget=1)
        assert len(cands) == 1 and cands[0].overflows


# ---------------------------------------------------------------------------
# Latency model
# ---------------------------------------------------------------------------

class TestLatencyModel:
    def test_cost_terms_shape(self):
        plan = tiling.plan_uniform_tiles(
            GEOM.in_spatial, GEOM.kernel, GEOM.stride, GEOM.cin, GEOM.cout)
        terms = tiling.plan_cost_terms(
            plan, GEOM.in_spatial, GEOM.kernel, GEOM.stride,
            GEOM.cin, GEOM.cout)
        assert terms["grid_steps"] > 0
        assert terms["mxu_dispatches"] >= terms["grid_steps"]
        assert terms["flops"] > 0 and terms["hbm_bytes"] > 0
        assert tiling.modeled_cost(terms) > 0.0

    def test_rank_orders_by_model(self):
        model = tune.LatencyModel()
        cands = tune.candidate_plans(GEOM3)
        ranked = model.rank(cands, GEOM3)
        costs = [model.layer_seconds(p, GEOM3) for p in ranked]
        assert costs == sorted(costs)
        assert set(ranked) == set(cands)

    def test_calibrate_env_overrides(self, monkeypatch):
        monkeypatch.setenv("REPRO_PEAK_GFLOPS", "123.0")
        monkeypatch.setenv("REPRO_MEM_GBPS", "45.0")
        model = tune.LatencyModel.calibrate()
        assert model.peak_flops == pytest.approx(123.0e9)
        assert model.mem_bps == pytest.approx(45.0e9)


# ---------------------------------------------------------------------------
# The tuner: seeded determinism, never-slower guarantee
# ---------------------------------------------------------------------------

class TestTuner:
    def test_model_only_tuning_is_deterministic(self):
        a = tune.tune_layer(GEOM3, trials=8, measure_topk=0, seed=7)
        b = tune.tune_layer(GEOM3, trials=8, measure_topk=0, seed=7)
        assert a.plan == b.plan
        assert a.scored == b.scored
        assert a.entry.to_json() == b.entry.to_json()

    def test_model_winner_never_modeled_worse_than_heuristic(self):
        # the heuristic is seeded into every scored pool, so even a
        # sampled search cannot rank a modeled-worse plan first
        model = tune.LatencyModel()
        for seed in range(3):
            res = tune.tune_layer(GEOM3, trials=4, measure_topk=0,
                                  seed=seed, model=model)
            assert (model.layer_seconds(res.plan, GEOM3)
                    <= model.layer_seconds(res.heuristic, GEOM3) + 1e-15)

    def test_measured_winner_never_slower_than_heuristic(self):
        res = tune.tune_layer(GEOM, trials=4, measure_topk=1, repeats=2)
        assert res.entry.measured_s > 0.0
        assert res.entry.heuristic_measured_s > 0.0
        # min() over a pool that always contains the heuristic
        assert res.entry.measured_s <= res.entry.heuristic_measured_s
        assert res.entry.winner_source in ("measured", "heuristic")

    def test_tune_network_dedups_geometries_and_skips_cached(self):
        chain = _chain()
        cache, results = tune.tune_network(chain, trials=4, measure_topk=0)
        assert len(cache) == len(results) == len(
            tune.network_geometries(chain))
        # second sweep over the same cache: nothing new to search
        cache2, results2 = tune.tune_network(chain, trials=4,
                                             measure_topk=0, cache=cache)
        assert cache2 is cache and results2 == []


# ---------------------------------------------------------------------------
# The cache: round-trip, budget refusal, schema invalidation
# ---------------------------------------------------------------------------

class TestTunedPlanCache:
    def _filled(self):
        cache, _ = tune.tune_network(_chain(), trials=4, measure_topk=0)
        cache.meta["note"] = "t"
        return cache

    def test_round_trip(self, tmp_path):
        cache = self._filled()
        path = cache.save(tmp_path / "tuned.json")
        loaded = tune.TunedPlanCache.load(path, strict=True)
        assert len(loaded) == len(cache)
        assert loaded.meta["note"] == "t"
        for key, entry in cache.entries.items():
            assert loaded.entries[key].plan == entry.plan
            assert loaded.entries[key].to_json() == entry.to_json()

    def test_lookup_refuses_over_budget_plans(self):
        cache = tune.TunedPlanCache()
        plan = tiling.plan_uniform_tiles(
            GEOM.in_spatial, GEOM.kernel, GEOM.stride, GEOM.cin, GEOM.cout)
        cache.put(GEOM.key_tuple, plan)
        assert cache.lookup(GEOM.key_tuple) == plan
        # a cache tuned at 8 MiB must not hand this plan to a tiny engine
        assert cache.lookup(GEOM.key_tuple,
                            vmem_budget=plan.step_vmem_bytes - 1) is None
        assert cache.lookups == 2 and cache.hits == 1

    def test_schema_version_mismatch_invalidates_silently(self, tmp_path):
        cache = self._filled()
        payload = cache.to_json()
        payload["schema_version"] = tune.SCHEMA_VERSION + 1
        path = tmp_path / "stale.json"
        path.write_text(json.dumps(payload))
        loaded = tune.TunedPlanCache.load(path)
        assert len(loaded) == 0
        assert loaded.meta["invalidated_version"] == tune.SCHEMA_VERSION + 1

    def test_schema_version_mismatch_raises_under_strict(self, tmp_path):
        payload = {"schema_version": 0, "entries": {}}
        path = tmp_path / "stale.json"
        path.write_text(json.dumps(payload))
        with pytest.raises(tune.TunedPlanSchemaError):
            tune.TunedPlanCache.load(path, strict=True)

    def test_entry_json_is_lossless(self):
        plan = dataclasses.replace(
            tiling.plan_uniform_tiles(GEOM.in_spatial, GEOM.kernel,
                                      GEOM.stride, GEOM.cin, GEOM.cout),
            modeled_cost=1.25e-6)
        entry = TunedEntry(plan=plan, modeled_s=1e-6, measured_s=2e-6,
                           heuristic_measured_s=3e-6, trials=4,
                           candidates=9, seed=1, winner_source="measured")
        back = TunedEntry.from_json(entry.to_json())
        assert back == entry
        assert back.plan.modeled_cost == plan.modeled_cost


# ---------------------------------------------------------------------------
# Engine integration: tuned_hit vs heuristic fallback, zero-search reload
# ---------------------------------------------------------------------------

class TestEngineIntegration:
    def test_plan_consults_tuned_cache_before_heuristic(self):
        cache, _ = tune.tune_network(_chain(), trials=4, measure_topk=0)
        tel = obs.Telemetry.create()
        eng = UniformEngine(EngineConfig(method="pallas",
                                         tuned_plans=cache, telemetry=tel))
        geoms = tune.network_geometries(_chain())
        for g in geoms:
            eng.plan(g.mode, g.in_spatial, g.kernel, g.stride, g.cin,
                     g.cout)
        assert eng.plan_sources == {"tuned": len(geoms), "heuristic": 0}
        assert tel.registry.get(
            "engine_plan_tuned_hits_total").value == len(geoms)
        assert tel.registry.get("engine_plan_heuristic_total") is None

    def test_metrics_distinguish_tuned_hit_from_heuristic(self):
        tel = obs.Telemetry.create()
        eng = UniformEngine(EngineConfig(method="pallas",
                                         tuned_plans=tune.TunedPlanCache(),
                                         telemetry=tel))
        eng.plan(GEOM.mode, GEOM.in_spatial, GEOM.kernel, GEOM.stride,
                 GEOM.cin, GEOM.cout)
        assert eng.plan_sources == {"tuned": 0, "heuristic": 1}
        assert tel.registry.get("engine_plan_heuristic_total").value == 1
        assert tel.registry.get("engine_plan_tuned_hits_total") is None
        # memo hit: neither source counter moves again
        eng.plan(GEOM.mode, GEOM.in_spatial, GEOM.kernel, GEOM.stride,
                 GEOM.cin, GEOM.cout)
        assert eng.plan_sources == {"tuned": 0, "heuristic": 1}
        assert tel.registry.get(
            "engine_plan_cache_hits_total").value == 1

    def test_over_budget_tuned_entry_falls_back_to_heuristic(self):
        cache = tune.TunedPlanCache()
        big = tiling.DeconvTilePlan(dtile=4, n_dtiles=1, block_ci=8,
                                    block_co=4, step_vmem_bytes=1 << 30,
                                    vmem_budget=1 << 30)
        cache.put(GEOM.key_tuple, big)
        eng = UniformEngine(EngineConfig(method="pallas",
                                         max_tile_bytes=256 * 1024,
                                         tuned_plans=cache))
        plan = eng.plan(GEOM.mode, GEOM.in_spatial, GEOM.kernel,
                        GEOM.stride, GEOM.cin, GEOM.cout)
        assert plan != big and not plan.overflows
        assert eng.plan_sources == {"tuned": 0, "heuristic": 1}

    def test_persisted_cache_reload_is_search_free_and_xla_parity(
            self, tmp_path):
        chain = _chain()
        cache, _ = tune.tune_network(chain, trials=8, measure_topk=0)
        path = cache.save(tmp_path / "tuned.json")

        loaded = tune.TunedPlanCache.load(path, strict=True)
        tel = obs.Telemetry.create()
        eng = UniformEngine(EngineConfig(method="pallas",
                                         tuned_plans=loaded, telemetry=tel))
        fn, report = compile_network(chain, eng)
        assert eng.plan_sources["heuristic"] == 0
        assert eng.plan_sources["tuned"] == len(eng.plan_cache) > 0
        assert tel.registry.get("engine_plan_heuristic_total") is None
        assert loaded.hits == loaded.lookups == len(eng.plan_cache)

        ws = init_network_weights(chain, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(1, *chain[0].in_spatial, chain[0].cin),
                        jnp.float32)
        xla_fn, _ = compile_network(chain, UniformEngine(method="xla"))
        np.testing.assert_allclose(np.asarray(fn(ws, x)),
                                   np.asarray(xla_fn(ws, x)),
                                   rtol=1e-4, atol=1e-4)

    def test_measure_plan_pins_the_candidate(self):
        cands = tune.candidate_plans(GEOM)
        wall = tune.measure_plan(cands[0], GEOM,
                                 vmem_budget=tiling.DECONV_VMEM_BUDGET,
                                 repeats=1)
        assert wall > 0.0
