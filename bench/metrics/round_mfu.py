"""Scoring FLOP/s of local training over the traced span, as a share of
the chip's peak, in %: forward and backward scores of the real (unpadded)
positive triples and their negatives, over the whole span's time.  A
client runs ``local_epochs`` epochs of ``train // batch`` full batches
(one short batch where it holds fewer triples than a batch)."""
import costs


def read(ctx):
    cfg, tr = ctx["cfg"], ctx["trace"]
    b = cfg["batch_size"]
    real = sum(cfg["local_epochs"] * max(1, n // b) * min(b, n)
               for n in ctx["shapes"]["train"])
    flops = ctx["work"]["rounds"] * costs.train_flops(
        cfg["method"], cfg["dim"], real, cfg["num_negatives"])
    return 100.0 * flops / tr.window_s / ctx["peak"]["flops_per_s"]
