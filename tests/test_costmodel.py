"""octwall tier-1 gate (Pass 4): compile-cost feature extraction, the
fitted model + its pinned calibration (the within-2x acceptance), the
compile_wall ratchet + pathology advisories, the registry drift gate,
and bench.py's attempt-2 estimate."""

import pytest

import jax
from jax import lax, numpy as jnp

from ouroboros_consensus_tpu.analysis import absint, costmodel, graphs



def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _unfenced_chain(depth):
    """The pre-PR-1 pathology shape: an unrolled multiply chain the
    algebraic simplifier's rewrite loop chews on superlinearly."""

    def fn(x):
        for _ in range(depth):
            x = x * x + x
        return x

    return fn


def _fenced_chain(depth):
    """The PR-1 remediation twin: the same chain behind a fori_loop
    fence (one small body computation, chain depth flat)."""

    def fn(x):
        return lax.fori_loop(0, depth, lambda _, v: v * v + v, x)

    return fn


# ---------------------------------------------------------------------------
# Feature extraction
# ---------------------------------------------------------------------------


def test_feature_extraction_counts_the_chain():
    f = costmodel.extract_features(
        jax.make_jaxpr(_unfenced_chain(50))(_sds(8)), "u"
    )
    assert f.eqns == 100
    assert f.mul_chain_depth == 50
    assert f.mul_count == 50
    assert f.computations == 1
    assert f.max_comp_eqns == 100
    assert f.fence_count == 0
    # the advisory provenance names THIS file
    assert "test_costmodel" in f.chain_src


def test_fence_resets_chain_and_attributes_the_body():
    f = costmodel.extract_features(
        jax.make_jaxpr(_fenced_chain(50))(_sds(8)), "f"
    )
    assert f.fence_count >= 1
    assert f.mul_chain_depth <= 3
    assert f.computations >= 2
    assert f.max_body_eqns >= 2
    # the monolith here IS the fence body, attributed to its source eqn
    assert f.monolith_src.startswith(("scan@", "while@", "pjit@"))


def test_features_match_pass2_metrics_on_a_registry_graph():
    """The cost walk mirrors graphs._analyze semantics: shared metrics
    must agree exactly on a real (small) registry graph."""
    tr = graphs.trace_graph("verdict_reduce")
    r = graphs.analyze_jaxpr(tr, "verdict_reduce")
    f = costmodel.extract_features(tr, "verdict_reduce")
    assert f.eqns == r.eqns
    assert f.computations == r.computations
    assert f.mul_chain_depth == r.mul_chain_depth
    assert f.op_fanout == r.op_fanout
    assert f.remat_width == r.remat_width


def test_feature_hash_stable_and_structure_sensitive():
    f = costmodel.extract_features(
        jax.make_jaxpr(_unfenced_chain(20))(_sds(8)), "a"
    )
    g = costmodel.extract_features(
        jax.make_jaxpr(_unfenced_chain(20))(_sds(8)), "b"
    )
    assert f.hash() == g.hash()  # name does not enter the hash
    h = costmodel.extract_features(
        jax.make_jaxpr(_unfenced_chain(21))(_sds(8)), "a"
    )
    assert f.hash() != h.hash()


# ---------------------------------------------------------------------------
# The fitted model + pinned calibration (the within-2x acceptance)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cost_json():
    return costmodel.load_cost()


def test_shipped_model_is_monotone_nonnegative(cost_json):
    model = cost_json["model"]
    assert model["rows"] >= 3
    for k, v in model["coeffs"].items():
        assert v >= 0, f"negative coefficient on {k}"
    # monotone: more structure never predicts a cheaper compile
    small = {k: 100 for k in costmodel.FEATURE_NAMES}
    big = {k: 10_000 for k in costmodel.FEATURE_NAMES}
    assert costmodel.predict(big, model) >= costmodel.predict(small, model)


def test_shipped_pins_are_consistent_with_the_model(cost_json):
    model = cost_json["model"]
    for name, pin in cost_json["graphs"].items():
        assert pin["feature_hash"] == costmodel.feature_hash(
            pin["features"]
        ), f"{name}: pinned hash does not match pinned features"
        pred = costmodel.predict(pin["features"], model)
        assert pin["predicted_s"] == round(pred, 1), \
            f"{name}: predicted_s pin is stale (re-run fit/--update-costs)"


def test_calibration_within_2x_on_80_percent(cost_json):
    """The acceptance criterion, validated offline from the pinned
    calibration rows (the same check `fit_costmodel.py --check` runs):
    predicted cold-compile wall within 2x of the measured first-execute
    on >= 80% of calibrated stages."""
    model = cost_json["model"]
    rows = cost_json["calibration"]
    assert len(rows) >= 10
    ok = 0
    for r in rows:
        ratio = costmodel.predict(r["features"], model) / max(
            1e-3, r["measured_s"]
        )
        ok += 0.5 <= ratio <= 2.0
    assert ok / len(rows) >= 0.8, f"only {ok}/{len(rows)} within 2x"


def test_every_registered_graph_is_pinned(cost_json):
    missing = set(graphs.registered_graphs()) - set(cost_json["graphs"])
    assert missing == set()


def test_fit_model_recovers_a_size_law():
    rows = [
        ({"eqns": e, "computations": 1, "max_comp_eqns": e,
          "mul_chain_depth": e // 2, "max_body_eqns": 0, "dot_count": 0},
         0.05 + e / 1000)
        for e in (100, 400, 1600, 6400, 25600)
    ]
    m = costmodel.fit_model(rows, backend="test")
    assert all(v >= 0 for v in m["coeffs"].values())
    for f, w in rows:
        assert 0.5 <= costmodel.predict(f, m) / w <= 2.0


def test_unfenced_chain_predicts_far_costlier_than_fenced_twin():
    """Regression fixture pinning the PR-1 remediation from the model
    side: the pre-remediation unfenced-multiply-chain shape must be
    predicted HIGH cost and its fori_loop-fenced twin LOW — if the
    model cannot separate them, the fit is meaningless."""
    unfenced = costmodel.extract_features(
        jax.make_jaxpr(_unfenced_chain(600))(_sds(32)), "unfenced"
    )
    fenced = costmodel.extract_features(
        jax.make_jaxpr(_fenced_chain(600))(_sds(32)), "fenced"
    )
    pu = costmodel.predict(unfenced)
    pf = costmodel.predict(fenced)
    assert pu is not None and pf is not None
    assert pu >= 3.0 * pf, (pu, pf)


# ---------------------------------------------------------------------------
# compile_wall ratchet + advisories
# ---------------------------------------------------------------------------


def test_check_compile_wall_flags_over_and_missing():
    f = costmodel.extract_features(
        jax.make_jaxpr(_unfenced_chain(600))(_sds(32)), "g"
    )
    budgets = {"compile_wall": {"graphs": {"g": {"predicted_s_max": 1e-6}}}}
    v = costmodel.check_compile_wall([f], budgets)
    assert len(v) == 1 and "exceeds budget" in v[0]
    assert costmodel.check_compile_wall([f], {"compile_wall": {}})
    ok = {"compile_wall": {"graphs": {"g": {"predicted_s_max": 1e9}}}}
    assert costmodel.check_compile_wall([f], ok) == []


def test_advisories_name_the_source_to_split():
    f = costmodel.extract_features(
        jax.make_jaxpr(_unfenced_chain(300))(_sds(32)), "g"
    )
    budgets = {"compile_wall": {"advisory": {
        "monolith_eqns": 100, "unfenced_chain": 100,
    }}}
    adv = costmodel.advisories(f, budgets)
    assert len(adv) == 2
    assert any("monolith computation" in a and "fence" in a for a in adv)
    assert any("unfenced multiply chain" in a and "test_costmodel" in a
               for a in adv)
    # a wall violation carries its advisories inline
    budgets["compile_wall"]["graphs"] = {"g": {"predicted_s_max": 1e-6}}
    v = costmodel.check_compile_wall([f], budgets)
    assert "unfenced multiply chain" in v[0]
    # and the detector fires on its own even when the wall fits
    budgets["compile_wall"]["graphs"] = {"g": {"predicted_s_max": 1e9}}
    v = costmodel.check_compile_wall([f], budgets)
    assert len(v) == 2
    assert all(x.startswith("g: ") for x in v)


def test_shipped_budgets_have_a_compile_wall_section():
    budgets = graphs.load_budgets()
    sec = budgets["compile_wall"]
    missing = set(graphs.registered_graphs()) - set(sec["graphs"])
    assert missing == set()
    assert sec["advisory"]["unfenced_chain"] >= 160  # over current max
    for name, cfg in sec["graphs"].items():
        assert cfg["predicted_s_max"] > 0


# ---------------------------------------------------------------------------
# Registry drift gate
# ---------------------------------------------------------------------------


def test_registry_drift_gate_clean_today():
    assert absint.check_registry_drift() == []


def test_registry_drift_gate_seeded(monkeypatch):
    """Seed the drift: a REGISTRY entry with no shapes.json spec and no
    GRAPH_SOURCES mapping must produce BOTH loud violations (it used to
    surface only as a KeyError deep inside certification)."""
    monkeypatch.setitem(graphs.REGISTRY, "ghost_graph", lambda t=None: None)
    v = absint.check_registry_drift()
    assert any("ghost_graph" in x and "shapes.json" in x for x in v)
    assert any("ghost_graph" in x and "GRAPH_SOURCES" in x for x in v)
    # aux drift is gated the same way
    monkeypatch.setitem(absint.AUX_REGISTRY, "ghost_aux",
                        lambda t=None: None)
    v = absint.check_registry_drift()
    assert any("ghost_aux" in x and "AUX_SOURCES" in x for x in v)


# ---------------------------------------------------------------------------
# Stage-name resolution + warmup note hashes
# ---------------------------------------------------------------------------


def test_stage_graph_resolution():
    assert costmodel.stage_graph("ed@b8192") == "ed_core"
    assert costmodel.stage_graph("agg-packed:304b") == "aggregate_core"
    assert costmodel.stage_graph("xla-packed:304b:p128") == \
        "verify_praos_core_bc"
    # draft-03 packed windows resolve to the NON-bc composed twin
    assert costmodel.stage_graph("xla-packed:256b:p80@64") == \
        "verify_praos_core"
    assert costmodel.stage_graph("unpack_a1b2c3@b8192") == "packed_unpack"
    # bit packing and a cast: no registered twin (`verdict_reduce` is
    # the retired on-device scan, traced for its goldens only)
    assert costmodel.stage_graph("reduce@b64") is None
    assert costmodel.stage_graph("something-new") is None


def test_check_pins_flags_drift_and_missing():
    """The one-sidedness closer: a graph whose current structure drifts
    from its costmodel.json pin (or has no pin) must fail the lint cost
    pass, so stage notes can never stamp walls with a stale hash."""
    pin = costmodel.pinned("packed_unpack")
    fresh = costmodel.CostFeatures(name="packed_unpack",
                                   **{k: pin["features"][k]
                                      for k in costmodel.FEATURE_NAMES})
    assert costmodel.check_pins([fresh]) == []
    drifted = costmodel.CostFeatures(name="packed_unpack",
                                     **{k: pin["features"][k]
                                        for k in costmodel.FEATURE_NAMES})
    drifted.eqns += 1
    (v,) = costmodel.check_pins([drifted])
    assert "drifted" in v and "--update-costs" in v
    ghost = costmodel.CostFeatures(name="no_such_graph")
    (v,) = costmodel.check_pins([ghost])
    assert "no costmodel.json pin" in v


def test_stage_feature_hash_joins_to_the_pin():
    pin = costmodel.pinned("ed_core")
    assert costmodel.stage_feature_hash("ed@b8192") == pin["feature_hash"]
    assert costmodel.stage_feature_hash("no-such-stage") is None


# ---------------------------------------------------------------------------
# bench.py consumers
# ---------------------------------------------------------------------------


def test_bench_attempt2_estimate_prefers_measured_then_model():
    import bench

    # a banked measured estimate wins
    assert bench._attempt2_estimate(123.0, 600.0) == 123.0
    # no banked estimate: the octwall model-predicted cold wall (the
    # shipped costmodel.json pins the production window programs)
    pred = bench._predicted_cold_wall()
    assert pred is not None and pred > bench._COLD_WALL_OVERHEAD_S
    assert bench._attempt2_estimate(None, 600.0) == pred
    assert bench._attempt2_estimate(0.0, 600.0) == pred


def test_bench_attempt2_estimate_falls_back_without_model(monkeypatch):
    import bench

    monkeypatch.setattr(bench, "_predicted_cold_wall", lambda: None)
    assert bench._attempt2_estimate(None, 600.0) == 300.0


def test_bench_cold_wall_refuses_partial_pins(monkeypatch):
    """A missing pin must yield None, not a partial sum: 4s of
    unpack/reduce standing in for the ~750s aggregate wall would let
    attempt 2 launch into exactly the death the gate exists to skip."""
    import bench

    monkeypatch.setattr(
        costmodel, "predicted_wall",
        lambda g: None if g == "aggregate_core" else 2.0,
    )
    assert bench._predicted_cold_wall() is None
