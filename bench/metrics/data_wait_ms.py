"""data_wait_ms: per cloud interval, the milliseconds in which the chip ran
no op (gaps of 20 us or more) while the engine thread waited for its next
batch block (span ``fed.prefetch_wait``, or the block's ``data.*`` work
where there is no worker thread), mean over the cell's chips
(``bench/scopes.py``). Nothing to read where the trace holds no engine
span."""
from bench import scopes


def read(ctx):
    return scopes.idle_ms(ctx, "data_wait")
