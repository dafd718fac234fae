"""Percent of the traced steps' device busy time of the Ouro cell that the join
of perfbench/segments.py could give to none of the six segments the step holds
nor to ``update``: instructions the compiled text does not hold, fusions of
several classes that no matmul or kernel decides, nameless instructions whose
consumers disagree. A text from another commit's cache entry reads 100."""


def read(ctx):
    from perfbench import segments

    return segments.unattributed_share(ctx)
