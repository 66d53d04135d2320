"""Share of the candidate scores the eval calls computed that rank a real
triple against its client's real entities, in %: the counters of the
program's ``eval`` spans (``scores_real`` over ``scores``), summed over the
window."""
import scopes


def read(ctx):
    return scopes.share("eval", "scores_real", "scores")
