"""step_grad_norm_ms: per cloud interval, the milliseconds of device self time
in the gradient norm the local step reports (scope
``hierfavg.local_step.grad_norm``), mean over the cell's chips
(``bench/scopes.py``). Nothing to read where no op carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "step_grad_norm_ms")
