"""Fail fast on non-finite training loss.

Parity with the ``chainer.training.extensions.FailOnNonNumber`` guard the
reference's users attached to distributed trainers: a NaN/Inf loss on ANY
process raises immediately instead of training garbage for hours (and in
the distributed case, instead of letting one diverged process drift from
the others).  Combined with :func:`add_global_except_hook`, the raise
tears down the whole job — the reference's crash-don't-deadlock model.

Runs as an ``observe`` hook, so EVERY iteration is checked regardless of
the extension's trigger, and it fails the iteration that broke: it reads
``float(main/loss)`` of the step just dispatched and so waits for that
step to end.  Nothing else in the trainer loop does (``LogReport`` keeps
a loss until the device has finished it), so attaching this guard to a
serial updater (``max_inflight=1``) puts feed, copy and step back in
series: the host may not begin the next batch before the step has ended.
What that costs is the feed's and the copy's share of the iteration: on
the TPU v5e, ResNet-50 at batch 256 through ``Trainer.run``, 153 ms an
iteration in series (device idle 35 %) against 100 ms overlapped, i.e.
1,661 against 2,547 images/s (PERF.md §6, PR 27).  Under
``max_inflight > 1`` the updater reports the retired window's loss and
the read costs nothing, at a lag of ``max_inflight`` updates.
"""

from __future__ import annotations

import math

__all__ = ["FailOnNonNumber"]


class FailOnNonNumber:
    """Raise ``RuntimeError`` when a watched observation goes non-finite.

    Args:
      keys: observation entries to watch (default: ``main/loss``).
    """

    priority = 400  # before log writers: fail the iteration that broke

    def __init__(self, keys=("main/loss",)):
        self.keys = tuple(keys)

    def observe(self, trainer):
        for key in self.keys:
            val = trainer.observation.get(key)
            if val is None:
                continue
            val = float(val)
            if not math.isfinite(val):
                raise RuntimeError(
                    f"non-finite {key} ({val}) at iteration "
                    f"{trainer.updater.iteration} — stopping before the "
                    "divergence trains further")

    def __call__(self, trainer):  # trigger path: same check
        self.observe(trainer)
