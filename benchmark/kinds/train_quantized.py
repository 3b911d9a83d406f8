"""Traffic kind ``train_quantized``: steady boosting on one Booster whose
trees are grown on QUANTIZED gradients (``use_quantized_grad``).

It IS ``kinds/train.py``'s ``run()`` — set-up counted from the process's
start, one ``Booster.update()`` per iteration fenced by a read-back, the
traced iterations inside the window, the reference only after the program's
state is dropped — handed two things from outside, since ``train.py`` has
no hook for them and may not be edited by the PR that added this file:

- the cell's Booster is ``lgb.Booster`` with two readings added: after each
  of the first ``checked_trees`` updates (all inside the warm-up:
  ``warmup_iters`` must cover them) and when the window's trees are dumped
  (after the window has closed) it reads what the program grew that tree
  on, ``bst._gbdt.last_levels()`` — the int8 gradient and hessian levels
  and the two scales, as ``last_sample()`` is read for a GOSS cell.  Never
  inside the window; the program's ``update()`` itself reads nothing back;
- ``compare.compare`` is ``compare_quantized.compare`` for the run, handed
  those levels: trees ``0 .. checked_trees - 1`` and the window's last.

Both are put back when the run ends.  A program that cannot say what it
grew a tree on (no ``GBDT.last_levels``: the commits before PR 37) cannot
run this kind: it says so and exits at once, before any data is made.

``tools/quantized_controls.py`` runs the cell with the program set to 2
levels or to round-to-nearest, the comparison unchanged: both must come
out not correct.
"""

from __future__ import annotations

from . import train
from .train import _log


def run(ctx: dict):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.gbdt import GBDT
    if not hasattr(GBDT, "last_levels"):
        _log("this program cannot say what a tree was grown on under "
             "quantized training (GBDT.last_levels is missing): the "
             "quantized comparison has nothing to recompute the checked "
             "trees from; not measuring")
        return None
    from .. import compare, compare_quantized

    traffic = ctx["traffic"]
    first = int(traffic["checked_trees"])
    if int(traffic["warmup_iters"]) < first:
        raise SystemExit(f"warmup_iters={traffic['warmup_iters']} does not "
                         f"cover the {first} checked trees read in set-up")
    levels = {}
    plain_booster, plain_compare = lgb.Booster, compare.compare

    class Booster(plain_booster):
        """The cell's Booster: reads the levels where no window is open."""

        _updates = 0

        def update(self, *a, **kw):
            done = super().update(*a, **kw)
            if self._updates < first:
                levels[self._updates] = self._gbdt.last_levels()
            self._updates += 1
            return done

        def dump_model(self, *a, **kw):
            model = super().dump_model(*a, **kw)
            levels[len(model["tree_info"]) - 1] = self._gbdt.last_levels()
            from lightgbm_tpu.telemetry import registry
            gauges = registry().snapshot()["gauges"]
            _log(f"plan {self._gbdt.plan}; gauges " + str({
                k: v for k, v in gauges.items()
                if k.startswith(("quant.", "hist."))}))
            return model

    def quantized_compare(config, data, trees, final_scores, seed,
                          checked_trees, stand_ins=False):
        return compare_quantized.compare(
            config, data, trees, final_scores, seed, sorted(levels), levels,
            stand_ins=stand_ins)

    lgb.Booster, compare.compare = Booster, quantized_compare
    try:
        return train.run(ctx)
    finally:
        lgb.Booster, compare.compare = plain_booster, plain_compare
