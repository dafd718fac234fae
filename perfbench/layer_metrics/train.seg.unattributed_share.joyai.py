"""Percent of the traced steps' device busy time of a JoyAI cell that the
family's join (perfbench/families/joyai/join.py: perfbench/segments.py's, and
the Ling family's rule: a fusion of several classes that no matmul or kernel
decides to the class of two thirds of its operations) could give to none of the
nine segments the cell's step holds nor to ``update``: instructions the compiled
text does not hold, fusions more evenly split than that (the table's and the
head's gradients, where the stack's use meets the module's), nameless
instructions whose consumers disagree. A text from another commit's cache entry
reads 100."""


def read(ctx):
    share = getattr(ctx.get("family"), "unattributed_share", None)
    return share(ctx) if share else None
