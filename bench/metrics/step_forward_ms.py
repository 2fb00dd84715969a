"""step_forward_ms: per cloud interval, the milliseconds of device self time
in the forward pass of the local step: ops under the program's
``hierfavg.local_step.grad`` scope with no ``transpose(`` on their path,
mean over the cell's chips (``bench/scopes.py``). Nothing to read where no
op carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "step_forward_ms")
