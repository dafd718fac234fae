"""A JoyAI cell's device time by segment: the join of
``perfbench/segments.py`` with the one rule more that the Ling family's
readers brought (``perfbench/families/ling3/join.py``, PR 39): a fusion of
several classes that no matmul or kernel decides goes to the class of two
thirds or more of the operations in it that say where they belong, else to
nobody as before. This family's step has the same stacked leaves whose
AdamW passes fuse with the ``pad`` and ``add_any`` that finish their
gradients, which the accepted join alone gives to nobody.

One copy of the rule: this module reads through that family's, found by
name as the harness finds any family, until a ``benchmark`` PR moves the
rule into ``perfbench/segments.py`` (PERF.md section 7, Sixth (a)), as the
Nemotron-H family's does. The segment readers ``train.seg.*.joyai`` and
``train.seg.unattributed_share.joyai`` read it, so they sum to the busy
time.
"""

from __future__ import annotations

from typing import Optional

from perfbench import harness


def _rule(ctx: dict):
    return harness.family(ctx["cell"]["paths"], "ling3").join


def segment_ms(ctx: dict, segment: str) -> Optional[float]:
    """Milliseconds a traced step spent under ``segment``."""
    return _rule(ctx).segment_ms(ctx, segment)


def unattributed_share(ctx: dict) -> Optional[float]:
    """Percent of the device's busy time that no segment got."""
    return _rule(ctx).unattributed_share(ctx)
