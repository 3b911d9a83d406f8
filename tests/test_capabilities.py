"""The learner-composition capability matrix (models/capabilities.py):
every warn-and-fallback / rejection decision is a declarative rule, and
this test enumerates the full (option-combination) space against the
matrix so no silently-degraded config exists outside it.  Reference
contrast: tree_learner.cpp:31-44 composes learners orthogonally."""

import dataclasses
import itertools

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.capabilities import RULES, Composition, resolve


def _comp(**kw):
    base = dict(voting=False, leaf_batch=1, mono_method="none",
                forced_splits=False, extra_trees=False,
                feature_fraction_bynode=False)
    base.update(kw)
    return Composition(**base)


def test_rule_names_unique_and_actions_valid():
    names = [r.name for r in RULES]
    assert len(names) == len(set(names))
    for r in RULES:
        assert r.action in ("error", "fallback")
        assert (r.fix is None) == (r.action == "error")


def test_matrix_enumeration_is_total():
    """Resolve the FULL boolean space: every outcome must be a fixed point
    (no rule still applies after resolve) or an error — i.e. the matrix
    is closed under its own fallbacks."""
    mono_methods = ("none", "basic", "intermediate", "advanced")
    flags = list(itertools.product((False, True), repeat=4))
    checked = errors = fallbacks = 0
    for mono in mono_methods:
        for voting, forced, extra, bynode in flags:
            for leaf_batch in (1, 16):
                comp = _comp(voting=voting, leaf_batch=leaf_batch,
                             mono_method=mono, forced_splits=forced,
                             extra_trees=extra,
                             feature_fraction_bynode=bynode)
                checked += 1
                try:
                    out, fired = resolve(comp)
                except ValueError:
                    errors += 1
                    continue
                fallbacks += bool(fired)
                for r in RULES:
                    if r.action == "fallback":
                        assert not r.applies(out), (r.name, comp)
    assert checked == 4 * 16 * 2
    assert errors and fallbacks        # both classes actually exercised


@pytest.mark.parametrize("kw,expect_voting,expect_batch,expect_fired", [
    # voting composes with per-node randomness/CEGB since round 5
    (dict(voting=True, extra_trees=True, leaf_batch=16), True, 16, False),
    (dict(voting=True, forced_splits=True, leaf_batch=16), False, 1, True),
    # monotone refresh composes with wave growth (conflict-free selection)
    (dict(mono_method="intermediate", leaf_batch=16), False, 16, False),
    (dict(mono_method="advanced", voting=True, leaf_batch=16), False, 16,
     True),
])
def test_fallback_outcomes(kw, expect_voting, expect_batch, expect_fired):
    out, fired = resolve(_comp(**kw))
    assert out.voting == expect_voting
    assert out.leaf_batch == expect_batch
    assert bool(fired) == expect_fired


@pytest.mark.parametrize("kw", [
    dict(mono_method="intermediate", extra_trees=True),
    dict(mono_method="advanced", feature_fraction_bynode=True),
    dict(mono_method="advanced", forced_splits=True),
])
def test_error_outcomes(kw):
    with pytest.raises(ValueError, match="does not compose"):
        resolve(_comp(**kw))


def test_gbdt_routes_through_matrix(capsys, tmp_path):
    """The driver's downgrades must be the matrix's downgrades (same
    messages, same effects)."""
    rng = np.random.RandomState(0)
    X = rng.rand(1500, 4)
    y = 2 * X[:, 0] + 0.1 * rng.randn(1500)
    import json
    forced_path = tmp_path / "forced.json"
    forced_path.write_text(json.dumps({"feature": 1, "threshold": 0.5}))
    bst = lgb.train({"objective": "regression", "num_leaves": 15,
                     "forcedsplits_filename": str(forced_path),
                     "tpu_leaf_batch": 8, "verbosity": 1},
                    lgb.Dataset(X, label=y), 2)
    out = capsys.readouterr()
    assert "tpu_leaf_batch=1" in out.out + out.err
    assert bst._gbdt.grower_cfg.leaf_batch == 1
    with pytest.raises(ValueError, match="extra_trees"):
        lgb.train({"objective": "regression", "num_leaves": 15,
                   "monotone_constraints": [1, 0, 0, 0],
                   "monotone_constraints_method": "intermediate",
                   "extra_trees": True, "verbosity": -1},
                  lgb.Dataset(X, label=y), 2)


# ---------------------------------------------------------------- the plan
# capabilities.plan_growth: ONE statement of what a configuration runs.
# The cases are the shapes the benchmark measures and the far side of every
# gate a queued configuration lands on; platform "tpu" unless said — the
# plan is a pure function of (config, mesh, shape, platform), so a CPU test
# can ask what a TPU would run.

from lightgbm_tpu.models.capabilities import plan_growth  # noqa: E402
from lightgbm_tpu.models.grower import GrowerConfig  # noqa: E402
from lightgbm_tpu.ops.split import SplitConfig  # noqa: E402

_PLAIN = SplitConfig(has_nan=True, has_categorical=False,
                     use_sorted_categorical=False, has_monotone=False)
_HIGGS = dict(rows=1_500_000, features=28)
_MSLTR = dict(rows=2_270_000, features=137)
_FORCED = ((0, 1, -1, -1),)


def _mesh(kind):
    from lightgbm_tpu.parallel.mesh import make_mesh
    return {None: None, "data4": make_mesh(4, 1),
            "feature4": make_mesh(1, 4)}[kind]


@pytest.mark.parametrize("cfg_kw,mesh,shape,platform,expect,why", [
    pytest.param(dict(leaf_batch=16), None, _HIGGS, "tpu",
                 dict(body="wave", layout="single", fused=True,
                      hist_impl="pallas", reduce="none", pool=False,
                      packed4=False, stream_reason=None), {},
                 id="higgs.train"),
    pytest.param(dict(leaf_batch=16), None, _MSLTR, "tpu",
                 dict(body="wave", layout="single", fused=False,
                      hist_impl="pallas"),
                 {"fused": "137 features: wave_layout admits up to 63"},
                 id="msltr.train"),
    pytest.param(dict(leaf_batch=1), None, _HIGGS, "tpu",
                 dict(body="wave", fused=True, hist_impl="pallas"), {},
                 id="higgs-leaf_batch1-tpu"),
    pytest.param(dict(leaf_batch=1), None, _HIGGS, "cpu",
                 dict(body="wave", fused=False, hist_impl="segment"),
                 {"fused": "platform cpu"}, id="higgs-leaf_batch1-cpu"),
    pytest.param(dict(leaf_batch=16), None, dict(rows=2048, features=28),
                 "tpu", dict(body="mask", layout="single", fused=False,
                             hist_impl="pallas"),
                 {"fused": "2048 rows"}, id="2048-rows-mask"),
    pytest.param(dict(leaf_batch=16), "data4", _HIGGS, "tpu",
                 dict(body="wave", layout="data", reduce="scatter",
                      fused=False, hist_impl="pallas"),
                 {"fused": "device mesh", "stream": "device mesh"},
                 id="data-mesh-scatter"),
    pytest.param(dict(leaf_batch=16, hist_comm="allreduce"), "data4", _HIGGS,
                 "tpu", dict(layout="data", reduce="psum"), {},
                 id="data-mesh-allreduce"),
    pytest.param(dict(leaf_batch=16, voting=True, histogram_pool_size=64.0),
                 "data4", _HIGGS, "tpu",
                 dict(layout="data", reduce="vote", pool=False),
                 {"pool": "voting", "scatter": "voting"},
                 id="data-mesh-voting"),
    pytest.param(dict(leaf_batch=16, histogram_pool_size=64.0), "data4",
                 _HIGGS, "tpu", dict(reduce="scatter", pool=True), {},
                 id="data-mesh-pool"),
    pytest.param(dict(leaf_batch=16, bundled=True, hist_bins=256), None,
                 _HIGGS, "tpu", dict(body="wave", fused=False),
                 {"fused": "EFB", "stream": "EFB"}, id="efb-unfused"),
    pytest.param(dict(leaf_batch=16, num_bins=16, packed4=True), None,
                 _HIGGS, "tpu", dict(packed4=True, fused=True), {},
                 id="max_bin15-packed4"),
    pytest.param(dict(leaf_batch=16, num_bins=16, packed4=True,
                      bundled=True, hist_bins=64), None, _HIGGS, "tpu",
                 dict(packed4=False), {"packed4": "EFB"},
                 id="packed4-not-under-efb"),
    pytest.param(dict(leaf_batch=1, num_bins=16, packed4=True,
                      gather_rows=False), "feature4", _HIGGS, "tpu",
                 dict(packed4=False, layout="feature"),
                 {"packed4": "feature shards"},
                 id="packed4-not-on-a-feature-mesh"),
    pytest.param(dict(leaf_batch=1, gather_rows=False), "feature4", _HIGGS,
                 "tpu", dict(body="wave", layout="feature", reduce="none",
                             fused=False, hist_impl="pallas"), {},
                 id="feature-mesh"),
    pytest.param(dict(leaf_batch=1, gather_rows=False,
                      split=dataclasses.replace(_PLAIN, use_cegb=True)),
                 "feature4", _HIGGS, "tpu",
                 dict(body="mask", layout="gspmd", hist_impl="onehot"),
                 {"feature": "CEGB"}, id="feature-mesh-cegb-gspmd-tpu"),
    pytest.param(dict(leaf_batch=1, gather_rows=False,
                      split=dataclasses.replace(_PLAIN, use_cegb=True)),
                 "feature4", _HIGGS, "cpu",
                 dict(body="mask", layout="gspmd", hist_impl="segment"),
                 {"feature": "CEGB"}, id="feature-mesh-cegb-gspmd-cpu"),
    pytest.param(dict(leaf_batch=4, gather_rows=False), "feature4", _HIGGS,
                 "tpu", dict(body="mask", layout="gspmd"),
                 {"feature": "wave of one"}, id="feature-mesh-leaf_batch4"),
    pytest.param(dict(leaf_batch=16, quantized=True), None,
                 dict(rows=1_500_000, features=200), "tpu",
                 dict(body="wave", fused=True), {}, id="quantised-int8-200"),
    pytest.param(dict(leaf_batch=16), None,
                 dict(rows=1_500_000, features=200), "tpu",
                 dict(body="wave", fused=False), {"fused": "200 features"},
                 id="f32-200-unfused"),
    pytest.param(dict(leaf_batch=1, forced_splits=_FORCED), None, _HIGGS,
                 "tpu", dict(body="wave", layout="single", fused=False),
                 {"fused": "forced splits", "stream": "forced splits"},
                 id="forced-splits"),
    pytest.param(dict(leaf_batch=16, wave_kernel="unfused"), None, _HIGGS,
                 "tpu", dict(body="wave", fused=False), {},
                 id="asked-unfused-is-no-refusal"),
    pytest.param(dict(leaf_batch=16, interaction_groups=((0, 1), (2, 3))),
                 None, _HIGGS, "tpu",
                 dict(stream_reason="interaction constraints", fused=False),
                 {"stream": "interaction constraints"},
                 id="stream-refused"),
])
def test_growth_plan(cfg_kw, mesh, shape, platform, expect, why):
    base = dict(num_leaves=255, num_bins=256, split=_PLAIN)
    cfg = GrowerConfig(**dict(base, **cfg_kw))
    plan = plan_growth(cfg, _mesh(mesh), "data", platform=platform, **shape)
    got = {k: getattr(plan, k) for k in expect}
    assert got == expect, str(plan)
    assert plan.hist_impl != "auto"
    for key, part in why.items():
        assert part in plan.why[key], (key, plan.why.get(key))
    # a refusal is said of what was asked for and not done, nothing else
    assert ("fused" in plan.why) is (not plan.fused
                                     and cfg.wave_kernel != "unfused")
    assert ("pool" in plan.why) is (not plan.pool
                                    and cfg.histogram_pool_size >= 0)
    if cfg.forced_splits:
        assert cfg.leaf_batch == 1      # RULES hold forced splits to W == 1


def test_growth_plan_rejects_unknown_names():
    base = dict(num_leaves=15, num_bins=64, split=_PLAIN)
    for kw, named in ((dict(histogram_impl="flat"),
                       "tpu_histogram_impl='flat': expected one of auto, "
                       "pallas, onehot, segment"),
                      (dict(wave_kernel="bogus"), "tpu_wave_kernel"),
                      (dict(hist_comm="bogus"), "tpu_hist_comm")):
        with pytest.raises(ValueError, match=named):
            plan_growth(GrowerConfig(**dict(base, **kw)), None,
                        rows=5000, features=4)
    with pytest.raises(ValueError, match="tpu_histogram_impl"):
        lgb.train({"objective": "binary", "tpu_histogram_impl": "flat",
                   "verbosity": -1},
                  lgb.Dataset(np.random.rand(100, 3), label=np.zeros(100)),
                  1)


_RUN = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "min_data_in_leaf": 5, "metric": "none",
        "tpu_histogram_impl": "pallas"}         # interpreted on the CPU


@pytest.mark.parametrize("rows,params", [
    pytest.param(6000, dict(tpu_leaf_batch=1, tpu_wave_kernel="unfused"),
                 id="wave-of-one"),
    pytest.param(6000, dict(tpu_leaf_batch=4, tpu_wave_kernel="unfused"),
                 id="wave-of-four"),
    pytest.param(6000, dict(tpu_leaf_batch=4, tpu_wave_kernel="fused"),
                 id="fused"),
    pytest.param(2000, dict(tpu_leaf_batch=4, tpu_wave_kernel="fused"),
                 id="mask"),
])
def test_plan_matches_what_runs(rows, params):
    """The plan is a claim about the program, so it is checked against the
    program: the phase scopes and the kernel launches of the traced
    iteration.  ``grow/wave_gather`` is there iff the plan says fused; the
    mask body hands every histogram launch all the rows, the wave body
    hands all but the root's a packed wave (``fused_wave_call``, or
    unfused ``histogram_ragged``)."""
    import jax
    from test_phase_scopes import _walk

    rng = np.random.RandomState(0)
    X = rng.randn(rows, 6).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = dict(_RUN, **params)
    ds = lgb.Dataset(X, label=y)
    ds.construct(params)
    g = lgb.Booster(params=params, train_set=ds)._gbdt
    assert g.fused_path_active
    mask, fmask, _ = g._iter_masks(None, None)
    jaxpr = jax.make_jaxpr(g._fused_core)(
        g.bins_dev, g.scores, mask, fmask, g.cfg.learning_rate).jaxpr
    gathers = launches = 0
    handed = set()
    for eqn, scope in _walk(jaxpr):
        gathers += "grow/wave_gather" in scope
        if eqn.params.get("name") in ("histogram_flat", "histogram_ragged"):
            launches += 1
            handed.add(eqn.invars[0].aval.shape[0])
    plan = g.plan
    assert plan.hist_impl == "pallas" and launches
    assert plan.fused is (gathers > 0), str(plan)
    assert g.wave_fused_active is plan.fused
    wave_ran = gathers > 0 or handed != {rows}
    assert (plan.body == "wave") is wave_ran, (str(plan), sorted(handed))
