"""ExchangePlan -> callable resolution for the core drivers.

``ExchangePlan`` itself lives in ``repro.common.types`` (it is pure data);
this module maps its ``histogram_impl`` field to the concrete reducer
callables the backends consume, importing the Pallas kernels only when
they are actually selected.
"""

from __future__ import annotations

from typing import Optional

from repro.common.types import ExchangePlan


def resolve_histogram_fns(plan: ExchangePlan, histogram_fn=None):
    """Map ``plan.histogram_impl`` to ``(histogram_fn, word_histogram_fn)``.

    - ``histogram_fn``: the per-EventLog local-combine reducer every
      backend accepts, or ``None`` for the backends' built-in
      ``site_week_histogram`` (the ``"segment_sum"`` impl).
    - ``word_histogram_fn``: the fused unpack+histogram hook the word-based
      MapReduce exchanges call directly on shuffled packed words
      (``mapreduce_histogram(word_histogram_fn=...)``), or ``None``.

    An explicit ``histogram_fn`` argument (a caller-supplied callable)
    always wins and disables the fused word path so the caller's function
    observes every record, matching the pre-plan contract.
    """
    if histogram_fn is not None:
        return histogram_fn, None
    if plan.histogram_impl == "pallas":
        from repro.kernels.segment_hist.ops import (
            segment_hist_eventlog,
            segment_hist_packed_words,
        )

        def word_fn(words, my_index, s_local, num_weeks, p):
            return segment_hist_packed_words(
                words, my_index, num_sites_local=s_local, num_partitions=p,
                num_weeks=num_weeks)

        return segment_hist_eventlog, word_fn
    return None, None


def expected_shuffle_rounds(plan: Optional[ExchangePlan],
                            shard_records: int, parts: int) -> int:
    """The static trip bound the compiled exchange while-loop must carry.

    An explicit ``plan.max_shuffle_rounds`` IS the bound; otherwise it is
    the provably sufficient ``ceil(shard_records / capacity)`` from the
    same ``static_capacity``/``shuffle_round_bound`` formulas the shuffle
    itself uses. The HLO pass (rule HL003) compares this against the trip
    bound parsed out of the compiled program — if they diverge, the
    compiled artifact is not running the exchange the plan describes.
    """
    from repro.core.backends.mapreduce import (
        shuffle_round_bound,
        static_capacity,
    )
    plan = plan or ExchangePlan()
    if plan.max_shuffle_rounds is not None:
        return plan.max_shuffle_rounds
    return shuffle_round_bound(
        shard_records,
        static_capacity(shard_records, parts, plan.capacity_factor))


def plan_fingerprint_fields(plan: Optional[ExchangePlan]) -> tuple:
    """The plan fields that change numerical results or their layout —
    folded into checkpoint fingerprints (``repro.core.resume``)."""
    plan = plan or ExchangePlan()
    return (plan.impl, plan.capacity_factor, plan.max_shuffle_rounds,
            plan.histogram_impl)
