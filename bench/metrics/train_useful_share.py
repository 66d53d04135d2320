"""Share of the client-steps the trainer ran that trained on a real batch,
in %: the counters of the program's ``round`` spans (``client_steps_real``
over ``client_steps``), summed over the window."""
import scopes


def read(ctx):
    return scopes.share("round", "client_steps_real", "client_steps")
