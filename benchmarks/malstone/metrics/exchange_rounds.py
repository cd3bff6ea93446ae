"""exchange_rounds: the MapReduce exchange (``repro.core.backends.mapreduce``).

``ShuffleStats.rounds`` of the window's first job: the most all_to_all rounds
any chunk needed. Nothing to read where the middleware keeps no such count.
"""


def read(ctx):
    return ctx.counters.get("rounds")
