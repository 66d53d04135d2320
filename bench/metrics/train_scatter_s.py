"""Own device seconds per round of the cotangent scatter-adds into the
entity and relation tables: scope ``feds/train/scatter``, plus the ops of
the training loop that carry no scope of their own.  On a TPU, XLA
rewrites each batched scatter-add (a sort of the row indices, then a
sorted scatter) without its op_name, and the profiler names those ops by
the loop they run in; the loop's few other unnamed ops (async slices of
the step's inputs, index iotas) are counted with them."""
import scopes


def read(ctx):
    s = scopes.read()
    if s is None:
        return None
    t = (s.own_s("feds/train/scatter") or 0.0) + s.loop_unnamed_s("feds/train")
    return t / ctx["work"]["rounds"] if t else None
