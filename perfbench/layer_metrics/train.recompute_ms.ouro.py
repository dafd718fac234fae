"""Milliseconds of device self time a traced step of the Ouro cell spends
making again, in the backward pass, what the checkpoints of its looped stack
dropped: each of the 32 layer applications (its projections, the flash
forward, the SwiGLU, its norms) and each of the four passes' head and loss.
Instructions whose matmuls or kernels, or else all of whose operations, lie
under JAX's ``rematted_computation`` on their ``op_name`` path
(perfbench/families/ouro/join.py). The price the loop pays for memory, and
what a policy that keeps more for the backward pass moves. Nothing where the
family has no such join."""


def read(ctx):
    spent = getattr(ctx.get("family"), "recompute_ms", None)
    return spent(ctx) if spent else None
