"""MalStone-as-a-service: a resident, incrementally-updatable engine.

``MalStoneService`` keeps the streaming engine's
:class:`~repro.core.streaming.HistogramState` **device-resident** in its
global layout (carry leaves sharded over the mesh via ``NamedSharding``)
and advances it in place: each ``ingest`` folds one global record chunk
through the SAME per-chunk backend dataflow the batch scan uses (for
``mapreduce`` that is the lossless multi-round counting exchange of the
``ExchangePlan``), so after any sequence of ingests the snapshot histogram
— and the accumulated ``ShuffleStats`` — are **bit-identical** to
``malstone_run_streaming`` over the same chunks. Queries never touch the
carry: a batch of N :class:`~repro.serve.queries.QuerySpec`\\ s is encoded
into mask rows and answered by ONE jitted dispatch over the resident
snapshot (masked ``windowed_ratio`` kernel + ``lax.top_k`` + drill-down
gather).

The client surface is split the way serverless executors split theirs
(invoker / wait / monitor): ``submit`` encodes and *dispatches* a query
batch and returns a ticket immediately (device execution is async under
JAX's dispatch); ``wait`` blocks on a ticket and decodes the answers;
``stats`` reports ingest/query accounting without blocking. ``query`` is
the synchronous convenience (``wait(submit(...))``).

Two ingest sources, mirroring ``malstone_run_streaming``'s modes:

- **log mode** — ``ingest(chunk)`` folds a materialized global chunk of
  ``parts * chunk_records`` records (device ``d`` takes block ``d``).
  ``ingest_slices`` cuts a full ``EventLog`` into exactly the (device,
  chunk) grouping the streaming scan uses, so chunk-by-chunk ingest of a
  log reproduces its one-shot streaming run bit-for-bit (including the
  ``rounds = max over chunks`` ShuffleStats field, which depends on that
  grouping).
- **seed mode** — ``ingest_chunks(k)`` regenerates the next ``k`` chunks
  per device from the MalGen seed on the mesh (device ``d`` folds chunks
  ``[d * cpd + done, d * cpd + done + k)`` — the resumable runner's
  segment layout), so no records ever cross the host.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.compat import shard_map
from repro.common.types import (
    EventLog,
    ExchangePlan,
    WEEKS_PER_YEAR,
    resolve_exchange_plan,
)
from repro.core.runner import (
    _STATS_SPEC,
    _axis_size,
    _finalize,
    _log_pspec,
    _pad_sites,
    _raise_if_exhausted,
    pad_log_to,
)
from repro.core.streaming import (
    HistogramState,
    STREAM_BACKENDS,
    fold_chunk,
    fold_chunk_range,
    snapshot,
    state_partition_spec,
    state_to_global,
    state_to_local,
    state_zeros_host,
)
from repro.serve.queries import (
    QueryBatch,
    QuerySpec,
    batched_query,
    decode_answers,
    encode_query_batch,
)


def ingest_slices(log: EventLog, parts: int,
                  chunk_records: int) -> Iterator[EventLog]:
    """Cut a full log into global ingest chunks matching the streaming
    scan's (device, chunk) grouping exactly.

    The streaming runner hands device ``d`` the contiguous shard
    ``log[d*per_dev : (d+1)*per_dev]`` and scans it in ``chunk_records``
    steps; ingest chunk ``j`` therefore concatenates, over devices, each
    device's j-th chunk — so that when the chunk is sharded over the
    record dim, device ``d`` folds the same rows in the same order as the
    batch scan (this is what makes the ``mapreduce`` per-chunk shuffle,
    and its ``rounds``/``bytes_exchanged`` accounting, bit-identical).
    Short logs are padded with invalid rows, exactly like
    ``malstone_run_streaming``.
    """
    stride = parts * chunk_records
    per_dev = -(-log.num_records // stride) * chunk_records
    log = pad_log_to(log, per_dev * parts)
    cols = [None if c is None else np.asarray(c) for c in log]
    for j in range(per_dev // chunk_records):
        idx = np.concatenate([
            np.arange(d * per_dev + j * chunk_records,
                      d * per_dev + (j + 1) * chunk_records)
            for d in range(parts)])
        yield EventLog(*(None if c is None else c[idx] for c in cols))


@dataclasses.dataclass
class ServiceStats:
    """Non-blocking service accounting (``MalStoneService.stats()``)."""

    chunks_folded: int            # per-device chunk cursor
    records_ingested: int         # global rows folded (incl. padding rows)
    ingest_calls: int
    batches_submitted: int
    batches_answered: int
    queries_submitted: int        # individual QuerySpecs across batches
    queries_answered: int
    pending: int                  # batches submitted but not yet waited


@dataclasses.dataclass
class _PendingBatch:
    batch: QueryBatch
    outputs: tuple                # device arrays, dispatch in flight


class MalStoneService:
    """Always-on incremental MalStone engine over a device mesh.

    Build once per (mesh, backend, chunk size) configuration — the ingest,
    snapshot, and query programs are jitted and cached on the instance, so
    a long-lived service pays compilation once per shape.

    ``seed`` / ``cfg`` / ``num_chunks`` enable seed-mode ingest
    (``ingest_chunks``); log-mode ``ingest`` is always available.
    ``kernel_path`` selects the query reducer: ``"pallas"`` (the masked
    ``windowed_ratio`` kernel; compiled on TPU, interpreted elsewhere —
    ``repro.kernels.resolve_interpret``; ``interpret`` forces either),
    ``"ref"`` (jnp einsum), or ``"auto"`` (pallas).
    """

    def __init__(self, *,
                 mesh: Mesh,
                 num_sites: int,
                 chunk_records: int,
                 backend: str = "streams",
                 num_weeks: int = WEEKS_PER_YEAR,
                 axis_name="data",
                 seed=None,
                 cfg=None,
                 num_chunks: Optional[int] = None,
                 plan: Optional[ExchangePlan] = None,
                 histogram_fn=None,
                 kernel_path: str = "auto",
                 interpret: Optional[bool] = None):
        if backend not in STREAM_BACKENDS:
            raise ValueError(
                f"unknown streaming backend {backend!r}; "
                f"have {STREAM_BACKENDS}")
        if chunk_records <= 0:
            raise ValueError(f"chunk_records must be > 0, got {chunk_records}")
        plan = resolve_exchange_plan(plan, _caller="MalStoneService")

        self.mesh, self.axis_name = mesh, axis_name
        self.backend, self.plan = backend, plan
        self.num_sites, self.num_weeks = num_sites, num_weeks
        self.chunk_records = chunk_records
        self.parts = _axis_size(mesh, axis_name)
        self.s_pad = _pad_sites(num_sites, self.parts)
        self._histogram_fn = histogram_fn
        self._fold_kw = dict(
            backend=backend, s_pad=self.s_pad, num_weeks=num_weeks,
            axis_name=axis_name, histogram_fn=histogram_fn, plan=plan)

        if kernel_path == "auto":
            kernel_path = "pallas"
        if kernel_path not in ("pallas", "ref"):
            raise ValueError(
                f"unknown kernel_path {kernel_path!r}; "
                f"have ('auto', 'pallas', 'ref')")
        self.kernel_path = kernel_path
        self.interpret = interpret

        # seed-mode configuration (optional)
        self.seed, self.cfg = seed, cfg
        self.cpd = None
        if seed is not None or cfg is not None or num_chunks is not None:
            if seed is None or cfg is None or num_chunks is None:
                raise ValueError(
                    "seed-mode ingest needs all of seed=, cfg= and "
                    "num_chunks=")
            if num_chunks % self.parts != 0:
                raise ValueError(
                    f"num_chunks ({num_chunks}) must divide over the mesh "
                    f"({self.parts} devices)")
            self.cpd = num_chunks // self.parts

        self._sspec = state_partition_spec(backend, axis_name)
        self._shardings = self._state_shardings()
        self._ingest_fns: dict = {}
        self._seed_ingest_fns: dict = {}
        self._snapshot_fn = None
        self._state = self._device_zero_state()

        # snapshot cache: refreshed lazily, invalidated by ingest/reset
        self._dirty = True
        self._hist_dev = None          # device [num_sites, W, 2] snapshot
        self._last_shuffle_stats = None

        # accounting + ticket book
        self._records_ingested = 0
        self._ingest_calls = 0
        self._queries_submitted = 0
        self._queries_answered = 0
        self._batches_submitted = 0
        self._batches_answered = 0
        self._next_ticket = 0
        self._pending: dict = {}

    # ----------------------------------------------------------- state mgmt
    def _state_shardings(self):
        """Per-leaf NamedShardings for the global-layout resident state.

        ``PartitionSpec`` subclasses ``tuple``, so a naive ``jax.tree.map``
        over the spec tree would descend INTO each spec; ``flatten_up_to``
        against the state's treedef keeps every spec whole.
        """
        zero = state_zeros_host(self.backend, self.parts, self.s_pad,
                                self.num_weeks)
        treedef = jax.tree.structure(zero)
        specs = treedef.flatten_up_to(self._sspec)
        return treedef, [NamedSharding(self.mesh, s) for s in specs]

    def _device_zero_state(self) -> HistogramState:
        treedef, shardings = self._shardings
        zero = state_zeros_host(self.backend, self.parts, self.s_pad,
                                self.num_weeks)
        leaves = treedef.flatten_up_to(zero)
        return jax.tree.unflatten(
            treedef,
            [jax.device_put(leaf, s) for leaf, s in zip(leaves, shardings)])

    def reset(self) -> None:
        """Zero the resident state and the ingest accounting (query
        counters and the compiled programs survive)."""
        self._state = self._device_zero_state()
        self._records_ingested = 0
        self._ingest_calls = 0
        self._dirty = True

    @property
    def chunks_folded(self) -> int:
        """Per-device chunk cursor (blocks on the int32 scalar only)."""
        return int(np.asarray(self._state.chunks_folded))

    # -------------------------------------------------------------- ingest
    def _log_ingest_fn(self, chunk: EventLog):
        # one compiled program per optional-column signature (event_seq /
        # shard_hash presence changes the pytree structure)
        key = tuple(col is not None for col in chunk)
        if key not in self._ingest_fns:
            kw = self._fold_kw

            def local(state_shard, chunk_shard):
                s = fold_chunk(state_to_local(state_shard), chunk_shard, **kw)
                return state_to_global(s)

            fn = shard_map(local, mesh=self.mesh,
                           in_specs=(self._sspec,
                                     _log_pspec(chunk, self.axis_name)),
                           out_specs=self._sspec, check_vma=False)
            self._ingest_fns[key] = jax.jit(fn)
        return self._ingest_fns[key]

    def ingest(self, chunk: EventLog) -> None:
        """Fold one global record chunk (``parts * chunk_records`` rows,
        device ``d`` takes block ``d``) into the resident state. Returns
        immediately — the fold is dispatched asynchronously."""
        expected = self.parts * self.chunk_records
        if chunk.num_records != expected:
            raise ValueError(
                f"ingest chunk has {chunk.num_records} records; this "
                f"service folds {expected} per ingest ({self.parts} "
                f"devices x {self.chunk_records}); use ingest_slices / "
                f"ingest_log to cut a full log")
        if chunk.valid is None:
            chunk = chunk._replace(
                valid=jnp.ones((chunk.num_records,), bool))
        self._state = self._log_ingest_fn(chunk)(self._state, chunk)
        self._records_ingested += expected
        self._ingest_calls += 1
        self._dirty = True

    def ingest_log(self, log: EventLog) -> int:
        """Cut ``log`` with :func:`ingest_slices` and ingest every chunk;
        returns the number of chunks folded."""
        n = 0
        for chunk in ingest_slices(log, self.parts, self.chunk_records):
            self.ingest(chunk)
            n += 1
        return n

    def _seed_ingest_fn(self, k: int):
        if k not in self._seed_ingest_fns:
            seed, cfg, cpd = self.seed, self.cfg, self.cpd
            axis, kw = self.axis_name, self._fold_kw
            chunk_records = self.chunk_records

            def local(state_shard):
                s = state_to_local(state_shard)
                first = jax.lax.axis_index(axis) * cpd + s.chunks_folded
                s = fold_chunk_range(s, seed, cfg, first, k, chunk_records,
                                     **kw)
                return state_to_global(s)

            fn = shard_map(local, mesh=self.mesh, in_specs=(self._sspec,),
                           out_specs=self._sspec, check_vma=False)
            self._seed_ingest_fns[k] = jax.jit(fn)
        return self._seed_ingest_fns[k]

    def ingest_chunks(self, k: int = 1) -> None:
        """Seed mode: regenerate and fold the next ``k`` chunks per device
        on the mesh (device ``d`` folds chunks ``[d*cpd + done, +k)`` — the
        resumable runner's segment layout, so any ingest schedule covering
        all ``cpd`` chunks is bit-identical to the one-shot streaming
        run)."""
        if self.cpd is None:
            raise ValueError(
                "this service was built without seed=/cfg=/num_chunks=; "
                "seed-mode ingest is unavailable (use ingest/ingest_log)")
        if k <= 0:
            raise ValueError(f"k must be > 0, got {k}")
        done = self.chunks_folded
        if done + k > self.cpd:
            raise ValueError(
                f"ingest_chunks({k}) overruns the configured stream: "
                f"{done} of {self.cpd} per-device chunks already folded")
        self._state = self._seed_ingest_fn(k)(self._state)
        self._records_ingested += k * self.parts * self.chunk_records
        self._ingest_calls += 1
        self._dirty = True

    # ------------------------------------------------------------ snapshot
    def _snapshot_jit(self):
        if self._snapshot_fn is None:
            backend = self.backend
            s_pad, num_weeks = self.s_pad, self.num_weeks
            axis = self.axis_name

            def local(state_shard):
                return snapshot(state_to_local(state_shard), backend=backend,
                                s_pad=s_pad, num_weeks=num_weeks,
                                axis_name=axis)

            out_specs = (P(), _STATS_SPEC if backend == "mapreduce" else None)
            fn = shard_map(local, mesh=self.mesh, in_specs=(self._sspec,),
                           out_specs=out_specs, check_vma=False)
            self._snapshot_fn = jax.jit(fn)
        return self._snapshot_fn

    def _refresh(self):
        """Re-materialize the resident snapshot if any ingest landed since
        the last one; queries read this cached device histogram."""
        if self._dirty or self._hist_dev is None:
            hist, stats = self._snapshot_jit()(self._state)
            if self.backend == "mapreduce":
                _raise_if_exhausted(jax.tree.map(np.asarray, stats))
                self._last_shuffle_stats = stats
            self._hist_dev = hist[:self.num_sites]
            self._dirty = False
        return self._hist_dev

    def snapshot(self):
        """(histogram, shuffle_stats): the replicated int32
        ``[num_sites, W, 2]`` histogram (numpy) and, for ``mapreduce``,
        the chunk-accumulated global ``ShuffleStats`` — bit-identical to
        ``malstone_run_streaming`` over the same chunks."""
        hist = np.asarray(self._refresh())
        stats = self._last_shuffle_stats
        if stats is not None:
            stats = jax.tree.map(np.asarray, stats)
        return hist, stats

    def result(self, statistic: str = "B"):
        """Finalize the resident snapshot as a full SpmResult (the batch
        drivers' ``malstone_a`` / ``malstone_b`` / ``b_fixed`` oracles)."""
        return _finalize(self._refresh(), statistic)

    # ------------------------------------------------------------- queries
    def submit(self, specs: Sequence[QuerySpec]) -> int:
        """Encode + dispatch a query batch; returns a ticket immediately
        (the device work proceeds under JAX async dispatch)."""
        batch = encode_query_batch(specs, self.num_weeks, self.num_sites)
        hist = self._refresh()
        outputs = batched_query(
            hist, jnp.asarray(batch.num_masks), jnp.asarray(batch.den_masks),
            jnp.asarray(batch.sites), max_top_k=batch.max_top_k,
            kernel_path=self.kernel_path, interpret=self.interpret)
        ticket = self._next_ticket
        self._next_ticket += 1
        self._pending[ticket] = _PendingBatch(batch=batch, outputs=outputs)
        self._batches_submitted += 1
        self._queries_submitted += len(batch.specs)
        return ticket

    def wait(self, ticket: int) -> list:
        """Block on one ticket; returns its decoded ``QueryAnswer`` list."""
        pending = self._pending.pop(ticket, None)
        if pending is None:
            raise KeyError(
                f"unknown or already-collected ticket {ticket!r}")
        answers = decode_answers(pending.batch, pending.outputs)
        self._batches_answered += 1
        self._queries_answered += len(answers)
        return answers

    def wait_all(self) -> dict:
        """Drain every in-flight ticket -> {ticket: answers}."""
        return {t: self.wait(t) for t in sorted(self._pending)}

    def query(self, specs: Sequence[QuerySpec]) -> list:
        """Synchronous convenience: ``wait(submit(specs))``."""
        return self.wait(self.submit(specs))

    # -------------------------------------------------------- introspection
    def ingest_program(self, k: int = 1):
        """The jitted seed-mode ingest program (global-layout state ->
        state). Public so the static-analysis jaxpr passes can stage the
        service's hot path without driving a real ingest; pair with
        :meth:`zero_state_host` for a traceable input."""
        if self.cpd is None:
            raise ValueError(
                "ingest_program needs seed-mode (seed=/cfg=/num_chunks=)")
        return self._seed_ingest_fn(k)

    def snapshot_program(self):
        """The jitted snapshot program (global-layout state ->
        (replicated histogram, shuffle stats)); see
        :meth:`ingest_program`."""
        return self._snapshot_jit()

    def zero_state_host(self):
        """Host-side zero ``HistogramState`` in this service's global
        layout — the trace/checkpoint aval for the programs above."""
        return state_zeros_host(self.backend, self.parts, self.s_pad,
                                self.num_weeks)

    # --------------------------------------------------------------- stats
    def stats(self) -> ServiceStats:
        return ServiceStats(
            chunks_folded=self.chunks_folded,
            records_ingested=self._records_ingested,
            ingest_calls=self._ingest_calls,
            batches_submitted=self._batches_submitted,
            batches_answered=self._batches_answered,
            queries_submitted=self._queries_submitted,
            queries_answered=self._queries_answered,
            pending=len(self._pending),
        )
