"""Milliseconds of device self time a traced step of the Ouro cell spends in
operations whose outermost segment is ``seg.exit``, forward and backward: the
exit gate's float32 logit after each of the four passes, the exit
distribution over the passes, its entropy and the passes' losses weighed by
it (``_exit_logits``, ``_exit_objective``); models/transformer.py.
perfbench/segments.py joins the trace's instruction names to the scopes
through the compiled step. Nothing where the program's vocabulary has no such
segment."""


def read(ctx):
    from perfbench import segments

    names = segments.vocabulary()
    if names is None or "seg.exit" not in names[0]:
        return None
    return segments.segment_ms(ctx, "seg.exit")
