"""step_optimizer_ms: per cloud interval, the milliseconds of device self time
in the optimizer's update and its application to the parameters (scope
``hierfavg.local_step.optimizer``), mean over the cell's chips
(``bench/scopes.py``). Nothing to read where no op carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "step_optimizer_ms")
