"""The job engine: compiles selected features into ONE jitted step.

Execution model (unchanged from the paper, Fig 2.1):

  * the *driver* is :func:`run_job` — it owns the ShardPlan, dispatches
    one jitted step per chunk, and commits progress through the sink;
  * the *executors* are the mesh devices: each processes its contiguous
    slice of records entirely locally (the HDFS-locality analogue);
  * the only collectives are the reduction merges (psums of the window
    partials declared by feature specs — the paper's final timestamp
    join, generalized to LTSA/SPD time resolutions).

What the API redesign changed is *what runs inside the step*: every
selected :class:`FeatureSpec` traces against one shared
:class:`FeatureContext`, so all features fuse into a single program and
a single pass over the data.

What the pipelined executor changes is *when things happen around the
step*.  The driver loop is a software pipeline over three resources —
host readers, devices, and the sink writer — instead of a serial chain:

  * the reduction accumulator (epoch aggregates AND the multi-window
    LTSA/SPD/extrema carries) lives ON-DEVICE as a jitted carry
    (``compile_reduce_update``), so no step blocks on a device→host
    sync; the accumulator is materialized once at job end, plus at the
    commit boundaries of sinks that persist it (async copies, off the
    critical path), where freshly-closed windows are finalized and
    flushed into the sink just before the commit that covers them;
  * up to ``ExecOptions.inflight`` steps stay in flight: step k+1 is
    dispatched while step k's outputs transfer to the host via
    ``copy_to_host_async`` and drain into the sink;
  * host-fed payloads arrive through ``Source.stream`` — which a
    :class:`~repro.api.sources.PrefetchSource` overlaps with compute via
    the SpeculativeLoader thread pool — and their device buffers are
    DONATED to the step so XLA can reuse/free them immediately; on the
    int16 transport path (``Source.payload_dtype == "int16"``) the
    payload ships as raw PCM (half the host→device bytes) plus a
    per-record decode-scale sidecar, and the Pallas kernels dequantize
    in VMEM — bitwise-identical to the float32 path;
  * an :class:`~repro.api.sinks.AsyncSink` (applied by the job builder)
    moves sink IO onto a background writer with the same ordering.

``ExecOptions()`` (the default) degenerates to the fully synchronous
loop.  Pipelining only reorders host-side waiting — the jitted programs
and their invocation order are identical — so sync and async results
are bitwise-equal (tests/test_async.py holds this line).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.manifest import DatasetManifest, ShardPlan
from repro.core import spans
from repro.core.params import DepamParams
from repro.distributed import partition as partition_lib
from .features import (EPOCH_WINDOW, FeatureContext, FeatureSpec,
                       Reduction, StateField, Window, selects_order_stats)
from .sinks import Sink
from .sources import Source, synth_record

# NOTE on payload donation: when no output can alias the donated
# waveform buffer, jax warns "Some donated buffers were not usable".
# The free still happens, so for this engine the message is noise — but
# suppressing it here would mutate process-global warning state for
# every importer, so the library leaves it alone (it prints at most
# once per process).  Applications that want silence filter it at their
# own entry point (launch/depam_run.py does; pyproject.toml covers the
# test suite).


@dataclasses.dataclass(frozen=True)
class ExecOptions:
    """Executor knobs; the default is the fully synchronous loop.

    ``inflight`` — device steps allowed in flight before the driver
    drains the oldest into the sink (0 = drain immediately, i.e. sync).
    ``prefetch_depth`` — plan steps of host read-ahead; the job builder
    wraps host-fed sources in a ``PrefetchSource`` of this depth (0 =
    fetch inline).  ``queue_size`` — AsyncSink backpressure bound, in
    steps.  ``donate`` — donate payload buffers and (when no sink needs
    per-step aggregate state) the on-device accumulator carry.
    """

    inflight: int = 0
    prefetch_depth: int = 0
    queue_size: int = 8
    donate: bool = True

    def __post_init__(self):
        if self.inflight < 0 or self.prefetch_depth < 0 \
                or self.queue_size < 1:
            raise ValueError(f"invalid ExecOptions: {self}")


@functools.lru_cache(maxsize=64)
def compile_step(specs: tuple[FeatureSpec, ...], m: DatasetManifest,
                 p: DepamParams, mesh: Mesh | None,
                 data_axes: tuple[str, ...], use_kernels: bool,
                 device_synth: bool, donate: bool = False,
                 payload_dtype: str = "float32") -> Callable:
    """Build the single jitted per-chunk step for all selected features.

    Takes (payload, mask) — or (payload, scales, mask) on the int16
    transport path — where payload is int32 indices (device synth),
    float32 waveforms, or raw ``<i2`` PCM, all with (n_shards, chunk)
    leading layout; ``scales`` is the per-record float32 decode-scale
    sidecar the kernels dequantize with in VMEM.  Returns
    {feature: (n_shards, chunk, *shape)} with padding slots overwritten
    by each spec's fill value.  ``donate`` hands the payload buffer to
    XLA (host-fed waveforms are the big one).

    Cached on the full configuration (specs are frozen dataclasses), so
    repeated jobs with the same setup reuse one compiled program instead
    of retracing per run.
    """
    consts = {s.name: {k: jnp.asarray(v) for k, v in s.setup(m, p).items()}
              for s in specs if s.setup is not None}
    raw = payload_dtype == "int16" and not device_synth

    def features_out(ctx, lead, mask):
        out = {}
        for s in specs:
            if s.ragged:
                # ragged feature: compute returns (counts, rows);
                # padding records are zeroed out of the counts so the
                # host-side compaction drops their rows entirely
                counts, rows = s.compute(ctx)
                counts = jnp.where(mask.reshape(-1), counts, 0)
                out[s.name] = {
                    "counts": counts.reshape(lead),
                    "rows": rows.reshape(lead + rows.shape[1:])}
                continue
            val = s.compute(ctx)
            val = val.reshape(lead + val.shape[1:])
            if s.shape is None:
                # reduction-only feature: never stored, so padding slots
                # need no fill — the reductions mask them to identities
                out[s.name] = val
                continue
            fmask = mask.reshape(lead + (1,) * (val.ndim - len(lead)))
            out[s.name] = jnp.where(fmask, val,
                                    jnp.asarray(s.fill, val.dtype))
        return out

    def local_step(payload, mask):
        if device_synth:
            records = jax.vmap(lambda i: synth_record(i, m))(
                payload.reshape(-1))
            records = records.reshape(*payload.shape, m.record_size)
        else:
            records = payload
        lead = records.shape[:-1]
        ctx = FeatureContext(records.reshape(-1, records.shape[-1]), p,
                             use_kernels, consts)
        return features_out(ctx, lead, mask)

    def local_step_raw(payload, scales, mask):
        lead = payload.shape[:-1]
        ctx = FeatureContext(payload.reshape(-1, payload.shape[-1]), p,
                             use_kernels, consts,
                             scales=scales.reshape(-1))
        return features_out(ctx, lead, mask)

    fn = local_step_raw if raw else local_step
    kw = {"donate_argnums": (0,)} if donate else {}
    if mesh is None:
        return jax.jit(fn, **kw)

    # XLA cannot partition a Mosaic kernel, so the step is written per
    # device: each one runs every feature on its own (shards / devices,
    # chunk, ...) slice, and the step holds no collective at all.
    # check_vma is off because pallas_call output shapes carry no
    # varying-axes annotation; every output is per-device by construction
    n_in = 3 if raw else 2
    fn = jax.shard_map(fn, mesh=mesh, in_specs=(P(data_axes),) * n_in,
                       out_specs=P(data_axes), check_vma=False)
    shard = NamedSharding(mesh, P(data_axes))
    return jax.jit(fn, in_shardings=(shard,) * n_in,
                   out_shardings=shard, **kw)


@dataclasses.dataclass(frozen=True)
class ReductionBinding:
    """One reduction resolved against a concrete window: the engine's
    unit of carry state.  Hashable (it keys the compile cache)."""

    feature: str                    # name of the feature value it reads
    red: Reduction
    wkey: str                       # resolved window routing key
    n_windows: int
    fields: tuple[StateField, ...]  # red.init(m, p), resolved once

    @property
    def out_name(self) -> str:
        return self.red.out_name

    @property
    def to_epoch(self) -> bool:
        """Declared-epoch reductions publish (squeezed) to
        ``JobResult.epoch``; everything else is a windowed output."""
        return self.red.window.kind == "epoch"


def _sk(b: "ReductionBinding", field: str) -> str:
    """Carry/commit key for one state field.  The ``__`` prefix marks it
    opaque to sinks (persisted verbatim, never interpreted); the window
    key is part of the identity, so resuming a cursor accumulated at a
    different window resolution fails the key match even when the
    window COUNT happens to coincide."""
    return f"__r:{b.wkey}:{b.out_name}:{field}"


def resolve_bindings(specs, m: DatasetManifest, p: DepamParams,
                     job_window: Window | None
                     ) -> tuple[tuple[ReductionBinding, ...],
                                dict[str, Window]]:
    """Bind every selected reduction to its concrete window.

    ``job``-window reductions resolve to ``job_window`` (epoch when the
    builder never called ``.window(...)``); returns the bindings plus
    the distinct resolved windows by routing key.
    """
    job_window = job_window or EPOCH_WINDOW
    bindings: list[ReductionBinding] = []
    windows: dict[str, Window] = {}
    owner: dict[str, str] = {}
    for s in specs:
        for red in s.reductions:
            win = job_window if red.window.kind == "job" else red.window
            if red.out_name in owner:
                raise ValueError(
                    f"reduction output {red.out_name!r} declared by both "
                    f"{owner[red.out_name]!r} and {s.name!r} — outputs "
                    f"must be unique across the selected features")
            owner[red.out_name] = s.name
            windows[win.key] = win
            bindings.append(ReductionBinding(
                feature=s.name, red=red, wkey=win.key,
                n_windows=win.n_windows(m), fields=tuple(red.init(m, p))))
    return tuple(bindings), windows


def _merged_segments(seg_op, contribs, wids, n_windows: int,
                     n_shards: int, combine):
    """Per-logical-shard window partials merged in fixed shard order.

    This is where the cross-device collective happens — and why sharded
    runs are bitwise-identical across device counts.  Each logical
    shard's contributions are segment-reduced *locally* (a vmap over
    the sharded leading axis, so every device reduces only its own
    rows), then the ``n_shards`` partials are combined in ascending
    shard order by an unrolled chain of ``combine`` ops.  Because the
    partial count and the merge order are fixed by the *plan* (not the
    mesh), laying the same plan over 1, 2, 4 or 8 devices changes only
    where the all-gather of the partials happens — pure data movement —
    never the order of a single floating-point add.

    ``n_shards == 1`` short-circuits to the plain global segment reduce
    (arithmetically the same chain), keeping single-shard jobs on the
    exact instruction sequence previous releases produced.
    """
    if n_shards == 1:
        return seg_op(contribs, wids.reshape(-1), num_segments=n_windows)
    c = contribs.reshape((n_shards, -1) + contribs.shape[1:])
    per = jax.vmap(
        lambda cc, ww: seg_op(cc, ww, num_segments=n_windows))(c, wids)
    part = per[0]
    for s in range(1, n_shards):
        part = combine(part, per[s])
    return part


@functools.lru_cache(maxsize=64)
def compile_reduce_update(bindings: tuple[ReductionBinding, ...],
                          mesh: Mesh | None, data_axes: tuple[str, ...],
                          donate: bool = False) -> Callable:
    """Multi-window carry update: state' = state ⊕ step contributions.

    Takes ``(state, outputs, mask, wids)`` and returns the new state.
    ``state`` maps ``__r:<window>:<out>:<field>`` to an
    ``(n_windows, *shape)`` array (plus ``:c`` Kahan companions for
    ksum fields and the ``__live__`` record count), living ON-DEVICE
    across the whole job.
    ``wids`` maps each distinct window key to the step's
    ``(n_shards, chunk)`` window ids (host-computed from the plan, so
    the program never retraces).  Each reduction's per-record
    contributions are segment-reduced per logical shard and merged into
    the carry in fixed shard order (see :func:`_merged_segments`);
    under a mesh the replicated out_sharding makes XLA insert the
    partial all-gather — the job's ONE collective per step, and the
    reason an N-device run is bitwise-identical to the 1-device run.
    ``donate`` recycles the old state's buffers — only safe when no
    per-step reference to the carry is kept (no sink consumes commit
    state).
    """

    def update(state, out, mask, wids):
        n_shards = mask.shape[0]
        fmask = mask.reshape(-1)
        new = {}
        for b in bindings:
            val = out[b.feature]
            val = val.reshape((-1,) + val.shape[2:])
            w = wids[b.wkey]
            contribs = b.red.update(val, fmask)
            for f in b.fields:
                key = _sk(b, f.name)
                c = contribs[f.name]
                if f.merge in ("sum", "ksum"):
                    part = _merged_segments(jax.ops.segment_sum, c, w,
                                            b.n_windows, n_shards, jnp.add)
                    if f.merge == "ksum":
                        y = part - state[key + ":c"]
                        t = state[key] + y
                        # zero partials are exact no-ops: without the
                        # where, the float32 (s, c) rotation would keep
                        # perturbing rows of already-CLOSED windows,
                        # breaking the byte-identity between rows
                        # flushed mid-job and the job-end recompute
                        zero = part == 0
                        new[key + ":c"] = jnp.where(
                            zero, state[key + ":c"],
                            (t - state[key]) - y)
                        new[key] = jnp.where(zero, state[key], t)
                    else:
                        new[key] = state[key] + part
                elif f.merge == "min":
                    new[key] = jnp.minimum(state[key], _merged_segments(
                        jax.ops.segment_min, c, w, b.n_windows, n_shards,
                        jnp.minimum))
                else:
                    new[key] = jnp.maximum(state[key], _merged_segments(
                        jax.ops.segment_max, c, w, b.n_windows, n_shards,
                        jnp.maximum))
        new["__live__"] = state["__live__"] \
            + jnp.sum(mask.astype(jnp.int32))
        return new

    kw = {"donate_argnums": (0,)} if donate else {}
    if mesh is None:
        return jax.jit(update, **kw)

    shard = NamedSharding(mesh, P(data_axes))
    rep = NamedSharding(mesh, P())
    return jax.jit(update, in_shardings=(rep, shard, shard, shard),
                   out_shardings=rep, **kw)


_STATE_DTYPES = {"float32": jnp.float32, "int32": jnp.int32}


def _init_reduce_state(bindings, resumed):
    """Device-resident multi-window carry, seeded from committed state.

    Every state field (including ksum compensations under ``:c`` keys)
    rides through commit/resume verbatim, so a resumed accumulation is
    bitwise-identical to an uninterrupted one.  A cursor whose aggregate
    keys do not exactly match the selected reductions is refused — a
    silent partial restore would publish wrong windows/aggregates.
    """
    state = {}
    for b in bindings:
        for f in b.fields:
            key = _sk(b, f.name)
            shape = (b.n_windows,) + tuple(f.shape)
            state[key] = jnp.full(shape, f.init, _STATE_DTYPES[f.dtype])
            if f.merge == "ksum":
                state[key + ":c"] = jnp.zeros(shape, jnp.float32)
    state["__live__"] = jnp.zeros((), jnp.int32)
    if resumed is not None:
        prev_agg, prev_live = resumed
        unknown = sorted(set(prev_agg) - set(state))
        missing = sorted(set(state) - set(prev_agg) - {"__live__"})
        if unknown or missing:
            raise ValueError(
                f"cannot resume: committed aggregate state does not "
                f"match the selected reductions (stale keys {unknown}, "
                f"absent keys {missing}) — the feature/reduction/window "
                f"set changed since the cursor was written, or the store "
                f"predates the windowed-reduction layout; use a fresh "
                f"store directory")
        state["__live__"] = jnp.asarray(int(prev_live), jnp.int32)
        for name, total in prev_agg.items():
            total = np.asarray(total)
            if total.shape != state[name].shape:
                raise ValueError(
                    f"cannot resume: committed aggregate {name!r} has "
                    f"shape {total.shape}, expected {state[name].shape} "
                    f"(window resolution or params changed since the "
                    f"cursor was written); use a fresh store directory")
            state[name] = jnp.asarray(total, state[name].dtype)
    return state


def _finalize_rows(b: ReductionBinding, host_state: dict,
                   lo: int, hi: int) -> np.ndarray:
    """Finalize window rows [lo, hi) of one binding on the host.

    The float32 carry is widened to float64 (exact) and ksum fields are
    compensation-corrected before ``finalize`` sees them, so mid-job
    flushes and the job-end pass produce byte-identical rows from the
    same committed state.
    """
    st = {}
    for f in b.fields:
        key = _sk(b, f.name)
        arr = np.asarray(host_state[key], np.float64)[lo:hi]
        if f.merge == "ksum":
            arr = arr - np.asarray(host_state[key + ":c"],
                                   np.float64)[lo:hi]
        st[f.name] = arr
    return np.asarray(b.red.finalize(st))


def _closed_windows(edges: np.ndarray, cursor: int) -> int:
    """How many leading windows lie entirely below the commit cursor."""
    return int(np.searchsorted(edges[1:], cursor, side="right"))


class Compiler:
    """Where a stepper gets its jitted artifacts from.

    The default instance simply calls the module-level (lru-cached)
    builders; :class:`repro.serve.CompileCache` implements the same two
    methods with service-level sharing and hit/miss accounting, so
    tenants of a :class:`~repro.serve.SoundscapeService` with matching
    configurations reuse one compiled program.
    """

    def step(self, specs, m, p, mesh, data_axes, use_kernels,
             device_synth, donate, payload_dtype) -> Callable:
        return compile_step(specs, m, p, mesh, data_axes, use_kernels,
                            device_synth, donate, payload_dtype)

    def reduce(self, bindings, mesh, data_axes, donate) -> Callable:
        return compile_reduce_update(bindings, mesh, data_axes, donate)


DEFAULT_COMPILER = Compiler()


class JobStepper:
    """One job as a resumable sequence of bounded step quanta.

    This is the schedulable unit the serving layer drives: ``run_job``
    (and ``SoundscapeJob.run``) execute ``start -> step_once* ->
    finish -> close`` back to back, while a
    :class:`~repro.serve.SoundscapeService` interleaves ``step_once``
    calls from many steppers over one device.  All per-job state — the
    on-device reduction carry, the in-flight dispatch queue, the source
    stream cursor and the window-flush watermarks — lives on the
    instance, so pausing a stepper between steps and resuming it later
    (or after a crash, through a resumable sink) is bitwise-identical
    to an uninterrupted run: the jitted programs and their invocation
    order per job never change, only the wall-clock interleaving does.

    Lifecycle: ``start()`` binds the source, compiles (through the
    pluggable ``compiler``), opens the sink and restores committed
    state; ``step_once()`` dispatches one plan step (returning False
    when none remain); ``finish()`` drains the pipeline and finalizes
    windows/epoch aggregates, returning the result tuple; ``close()``
    releases source/sink/stream unconditionally and must be called even
    when any other method raised.  ``poll()`` is the non-blocking
    readiness probe the scheduler uses to skip tenants whose live
    source has no data yet.
    """

    def __init__(self, m: DatasetManifest, p: DepamParams,
                 specs: list[FeatureSpec], source: Source, sink: Sink,
                 mesh: Mesh | None, data_axes: tuple[str, ...],
                 pl_: ShardPlan, use_kernels: bool,
                 max_steps: int | None = None,
                 options: ExecOptions | None = None,
                 window: Window | None = None,
                 compiler: Compiler | None = None,
                 quarantine=None, instrument=None):
        self.m = m
        self.p = p
        # calibration provenance (repro.meta.Instrument or None): handed
        # to the sink before open, so resumable sinks commit it with the
        # cursor and labeled sinks stamp it on output attrs
        self.instrument = instrument
        self.specs = tuple(specs)
        self.source = source
        self.sink = sink
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.pl = pl_
        self.use_kernels = use_kernels
        self.max_steps = max_steps
        self.options = options or ExecOptions()
        self.window = window
        self.compiler = compiler or DEFAULT_COMPILER
        # the job's bad-record set (repro.faults.Quarantine), shared
        # with the ResilientSource that populates it; None = strict mode
        # (any bad record fails the job)
        self.quarantine = quarantine
        self._started = False
        self._closed = False
        self._result = None
        self._exhausted = False      # live stream ended before the plan
        self._stream = None
        self._inflight: collections.deque = collections.deque()
        self._windows_out: dict[str, np.ndarray] = {}
        self._overflowed = False     # event-capacity warning fired once

    # -- lifecycle ------------------------------------------------------
    def start(self) -> "JobStepper":
        """Bind, compile, open the sink, restore committed state (the
        span ``start``, whose ``pct_select`` is 1 when the step selects
        the percentiles' order statistics and 0 when it sorts them or
        has none)."""
        pct_select = self.use_kernels \
            and any(s.name == "percentiles" for s in self.specs) \
            and selects_order_stats(self.p.frames_per_record)
        with spans.span("start", pct_select=int(pct_select)):
            return self._start()

    def _start(self) -> "JobStepper":
        """Bind, compile, open the sink, restore committed state.

        Resumable sinks may carry a committed plan whose geometry
        differs from this job's (the job was checkpointed under a
        different device count): the committed partition wins — the
        same logical ``(n_shards, chunk)`` program replays over however
        many devices the current mesh provides, which is what makes a
        resume across a changed device count bitwise-identical."""
        committed = self.sink.committed_plan()
        if committed is not None:
            self.pl = partition_lib.adopt_plan(self.pl, committed)
        m, p, pl_ = self.m, self.p, self.pl
        self._sharding = None
        if self.mesh is not None:
            n_dev = partition_lib.data_parallel_size(self.mesh,
                                                     self.data_axes)
            if n_dev > pl_.n_shards or pl_.n_shards % n_dev:
                raise ValueError(
                    f"plan has {pl_.n_shards} logical shard(s), which "
                    f"cannot be laid out over {n_dev} data-parallel "
                    f"device(s) (mesh {dict(self.mesh.shape)}, data axes "
                    f"{self.data_axes}) — the device count must divide "
                    f"the shard count; pick .shards(L) with L % devices "
                    f"== 0, or build a smaller mesh with "
                    f"make_host_mesh(data=...)")
            self._sharding = partition_lib.shard_sharding(self.mesh,
                                                          self.data_axes)
        self.source = source = self.source.bind(m, p)
        self._shapes = {s.name: tuple(s.shape(m, p)) for s in self.specs
                        if s.shape is not None}
        self._ragged = {s.name: s for s in self.specs if s.ragged}

        bindings, wins = resolve_bindings(self.specs, m, p, self.window)
        self._bindings = bindings
        self._wins = wins
        self._windowed = tuple(b for b in bindings if not b.to_epoch)
        self._edges = {b.out_name: wins[b.wkey].edges(m)
                       for b in self._windowed}

        self._raw = not source.device_synth \
            and source.payload_dtype == "int16"
        donate_payload = self.options.donate and not source.device_synth
        donate_carry = self.options.donate and not self.sink.wants_commit
        self._step_fn = self.compiler.step(
            self.specs, m, p, self.mesh, self.data_axes, self.use_kernels,
            source.device_synth, donate_payload, source.payload_dtype)
        self._agg_fn = self.compiler.reduce(
            bindings, self.mesh, self.data_axes, donate_carry)

        self.sink.set_instrument(self.instrument)
        self.sink.open(m, p, self._shapes, pl_)
        if self._windowed:
            self.sink.open_windows({
                b.out_name: (b.n_windows,) + tuple(b.red.out_shape(m, p))
                for b in self._windowed})
            # labeled sinks derive per-window time coordinates from
            # these record-offset edges (manifest.record_times)
            self.sink.open_window_edges(
                {name: e.copy() for name, e in self._edges.items()})
        if self._ragged:
            # capacity is a params knob (it keys the compiled program),
            # so every ragged feature of a job shares p.event_capacity
            self.sink.open_events({
                name: (s.columns, p.event_capacity)
                for name, s in self._ragged.items()})
        start_step, resumed = self.sink.resume_state()
        if resumed is not None:
            # the quarantine set rides the commit as an opaque agg key;
            # strip it before the strict reduction-key match and restore
            # it into this run's set, so resumed masking (and the spent
            # budget) is bitwise-identical to the uninterrupted run
            prev_agg, prev_live = resumed
            q = prev_agg.pop("__quarantine__", None)
            if q is not None and np.asarray(q).size:
                if self.quarantine is None:
                    raise ValueError(
                        f"cannot resume: the committed cursor carries "
                        f"{np.asarray(q).size} quarantined record(s) "
                        f"but this job does not tolerate bad records; "
                        f"re-run with .tolerate(bad_records="
                        f"{np.asarray(q).size}) or more, or use a "
                        f"fresh store directory")
                self.quarantine.seed(q)
            resumed = (prev_agg, prev_live)
        self._agg_state = _init_reduce_state(bindings, resumed)

        self._n_steps = pl_.n_steps if self.max_steps is None \
            else min(pl_.n_steps, self.max_steps)
        self._step = start_step

        # Windows already flushed durably: everything closed below the
        # committed cursor (their rows landed before that commit).
        start_cursor = pl_.cursor_after(start_step - 1) if start_step > 0 \
            else pl_.start
        self._flushed = {
            b.out_name: _closed_windows(self._edges[b.out_name],
                                        start_cursor)
            if start_step > 0 else 0
            for b in self._windowed}

        self._stream = None if source.device_synth \
            else source.stream(pl_, start_step, self._n_steps)
        self._started = True
        return self

    # -- progress -------------------------------------------------------
    @property
    def step(self) -> int:
        """The next plan step to dispatch."""
        return self._step if self._started else 0

    @property
    def n_steps(self) -> int:
        return self._n_steps if self._started else self.pl.n_steps

    @property
    def records_done(self) -> int:
        """Records covered by already-dispatched steps."""
        if not self._started or self._step == 0:
            return 0
        return self.pl.committed_records(self._step - 1)

    @property
    def done(self) -> bool:
        return self._started and (self._result is not None
                                  or self._exhausted
                                  or self._step >= self._n_steps)

    def _ship(self, x: np.ndarray):
        """Host payload -> device(s).  Under a mesh, each device gets
        only its shard's rows (device-local placement, the donated
        buffer already laid out for the step's in_sharding); without
        one, a plain transfer."""
        if self._sharding is None:
            return jnp.asarray(x)
        return partition_lib.ship(x, self._sharding)

    def _live_mask(self, idx: np.ndarray) -> np.ndarray | None:
        """The step's live mask, additionally excluding records a
        finite (ended) live stream will never deliver.  For every
        non-live source ``stream_end()`` is None and the plan mask
        passes through untouched — the bitwise anchor."""
        mask = self.pl.step_mask(self._step)
        end = self.source.stream_end()
        if end is not None:
            mask = mask & (idx < end)
        return mask

    def poll(self) -> str:
        """Non-blocking readiness: ``"ready"`` (step_once will not
        block on the source), ``"pending"`` (live source still waiting
        for data), or ``"done"`` (no steps left — the plan is finished
        or the live stream ended)."""
        if not self._started:
            return "ready"          # start() is the next unit of work
        if self.done:
            return "done"
        idx = self.pl.step_indices(self._step)
        mask = self._live_mask(idx)
        if not mask.any() and self.source.stream_end() is not None:
            return "done"
        return self.source.poll(idx[mask])

    def step_once(self) -> bool:
        """Dispatch one plan step (and drain past ``inflight``);
        returns False when no step remains."""
        assert self._started, "JobStepper.step_once before start()"
        if self.done:
            return False
        step = self._step
        pl_, source = self.pl, self.source
        idx = pl_.step_indices(step)
        mask = self._live_mask(idx)
        if not mask.any() and source.stream_end() is not None:
            # graceful end-of-stream: every remaining plan record lies
            # beyond what the live source will ever deliver
            self._exhausted = True
            return False
        payload = None
        if not source.device_synth:
            # fetch BEFORE freezing the mask: a tolerant source may
            # quarantine records of this very step while reading them
            with spans.span("fetch_wait", step=step, records=idx.size):
                payload = np.asarray(next(self._stream))
        with spans.span("dispatch", step=step) as sp:
            if self.quarantine is not None and len(self.quarantine):
                # quarantined records carry zero payloads; masking them
                # keeps them out of every reduction and leaves their
                # rows at the feature's fill value — reduction
                # identities, never a silently-wrong number
                mask = mask & ~self.quarantine.mask_for(idx)
            dmask = jnp.asarray(mask)
            wids = {k: jnp.asarray(w.ids(idx, self.m))
                    for k, w in self._wins.items()}
            if source.device_synth:
                shipped = (self._ship(np.asarray(idx, np.int32)),)
            elif self._raw:
                # raw-PCM transport: ship the int16 bytes as-is (half
                # the bus traffic, still donated) + the tiny per-record
                # decode-scale sidecar; kernels dequantize in VMEM
                if payload.dtype != np.int16:
                    raise TypeError(
                        f"int16 payload path got {payload.dtype} from "
                        f"{type(source).__name__}.stream — the source's "
                        f"payload_dtype promises raw '<i2' PCM")
                shipped = (self._ship(payload),
                           jnp.asarray(source.scales(idx), jnp.float32))
            else:
                shipped = (self._ship(payload.astype(np.float32,
                                                     copy=False)),)
            sp.set_metadata(h2d_bytes=sum(
                x.nbytes for x in (*shipped, dmask, *wids.values())))
            out = self._step_fn(*shipped, dmask)
            self._agg_state = self._agg_fn(self._agg_state, out, dmask,
                                           wids)
            # start the device→host transfers now; block in _drain_one
            # — reduction-only values never cross back to the host
            for name in self._shapes:
                out[name].copy_to_host_async()
            for name in self._ragged:
                out[name]["counts"].copy_to_host_async()
                out[name]["rows"].copy_to_host_async()
            commit_state = self._agg_state if self.sink.wants_commit \
                else None
            if commit_state is not None:
                for v in commit_state.values():
                    v.copy_to_host_async()
        self._inflight.append((step, idx, mask, out, commit_state))
        self._step += 1
        while len(self._inflight) > self.options.inflight:
            self._drain_one()
        return True

    # -- sink side ------------------------------------------------------
    def _flush_closed(self, step, commit_state, cursor):
        """Finalize + write every window the cursor just closed, BEFORE
        the commit that makes the cursor durable covers them."""
        with spans.span("flush_windows", step=step):
            for b in self._windowed:
                closed = _closed_windows(self._edges[b.out_name], cursor)
                if closed > self._flushed[b.out_name]:
                    rows = _finalize_rows(
                        b, commit_state, self._flushed[b.out_name], closed)
                    with spans.span("sink_put", step=step):
                        self.sink.write_windows(b.out_name,
                                                self._flushed[b.out_name],
                                                rows.astype(np.float32))
                    self._flushed[b.out_name] = closed

    def _drain_one(self):
        """Materialize the oldest in-flight step into the sink."""
        step, idx, mask, out, commit_state = self._inflight.popleft()
        with spans.span("drain", step=step):
            self._drain(step, idx, mask, out, commit_state)

    def _drain(self, step, idx, mask, out, commit_state):
        keep = mask.reshape(-1)
        sel = idx.reshape(-1)[keep]
        # carry persisted in its NATIVE dtypes (float32 / int32): resume
        # casts losslessly, _finalize_rows widens to float64 itself, and
        # the commit sidecar stays state-sized
        carry = {} if commit_state is None else commit_state
        pulled = [out[name] for name in self._shapes] \
            + [out[name][k] for name in self._ragged
               for k in ("counts", "rows")] + list(carry.values())
        with spans.span("d2h_wait", step=step,
                        d2h_bytes=sum(x.nbytes for x in pulled)):
            values = {name: np.asarray(out[name]) for name in self._shapes}
            slabs = {name: (np.asarray(out[name]["counts"]),
                            np.asarray(out[name]["rows"]))
                     for name in self._ragged}
            agg_host = {k: np.asarray(v) for k, v in carry.items()}
        values = {name: v.reshape((-1,) + self._shapes[name])[keep]
                  for name, v in values.items()}
        with spans.span("sink_put", step=step):
            self.sink.write(step, sel, values)
        if self._ragged:
            # host-side compaction: the device returned fixed-capacity
            # slabs; only the first min(count, capacity) rows of each
            # live record enter the append-only log (record order —
            # boolean take over (batch, capacity) preserves it)
            with spans.span("compact", step=step) as sp:
                ev = {}
                for name, (counts, rows) in slabs.items():
                    counts = counts.reshape(-1)[keep]
                    rows = rows.reshape((-1,) + rows.shape[-2:])[keep]
                    cap = rows.shape[1]
                    slot = np.arange(cap)[None, :] < \
                        np.minimum(counts, cap)[:, None]
                    ev[name] = (counts.astype(np.int32),
                                rows[slot].astype(np.float32, copy=False))
                    if not self._overflowed and (counts > cap).any():
                        self._overflowed = True
                        import warnings
                        warnings.warn(
                            f"event capacity overflow in feature "
                            f"{name!r}: some records detected more than "
                            f"{cap} events; only the first {cap} are kept "
                            f"(raise DepamParams.event_capacity or the "
                            f"threshold). Affected records have counts > "
                            f"capacity in the event log.", RuntimeWarning,
                            stacklevel=3)
                sp.set_metadata(events=sum(len(r) for _, r in ev.values()))
            with spans.span("sink_put", step=step):
                self.sink.write_events(step, sel, ev)
        if commit_state is not None:
            live = float(agg_host.pop("__live__"))
            if self.quarantine is not None:
                # snapshot of the bad-record set rides the commit as an
                # opaque key (bad records are deterministic-by-record,
                # so a snapshot that is "ahead" of this step's cursor
                # only pre-masks records that would re-fail anyway)
                agg_host["__quarantine__"] = self.quarantine.as_array()
            self._flush_closed(step, agg_host, self.pl.cursor_after(step))
            with spans.span("sink_put", step=step):
                self.sink.commit(self.pl, step, agg_host, live)

    def finish(self):
        """Drain the pipeline, finalize every window (trailing partial
        ones included) and the epoch aggregates; idempotent.

        Returns (features, epoch, windows, window_edges, n_records,
        events, plan, quarantine) — see job.JobResult.  ``events`` is
        the sink's materialized {name: EventLog} for ragged features
        (None when the job has none, or the sink streams);
        ``quarantine`` is the bad-record report dict (None unless the
        job tolerates bad records).  Rows flushed mid-job came from the
        same committed float32 state, so the job-end pass is
        byte-identical to them.
        """
        assert self._started, "JobStepper.finish before start()"
        if self._result is not None:
            return self._result
        while self._inflight:
            self._drain_one()
        host_state = {k: np.asarray(v) for k, v in self._agg_state.items()}
        last = self._step - 1
        with spans.span("flush_windows", step=last):
            for b in self._windowed:
                rows = _finalize_rows(b, host_state, 0, b.n_windows)
                self._windows_out[b.out_name] = rows.astype(np.float32)
                start = self._flushed[b.out_name]
                if start < b.n_windows:
                    with spans.span("sink_put", step=last):
                        self.sink.write_windows(
                            b.out_name, start,
                            self._windows_out[b.out_name][start:])
                    self._flushed[b.out_name] = b.n_windows

        live = int(host_state["__live__"])
        epoch = {}
        for b in self._bindings:
            if b.to_epoch:
                # single-window reductions publish squeezed, in float64
                epoch[b.out_name] = _finalize_rows(b, host_state, 0, 1)[0]
        window_edges = {name: self._edges[name].copy()
                        for name in self._windows_out}
        events = self.sink.event_result() if self._ragged else None
        qreport = None
        if self.quarantine is not None:
            qreport = self.quarantine.report()
            if qreport["records"]:
                import warnings
                warnings.warn(
                    f"{len(qreport['records'])} record(s) quarantined "
                    f"as bad data (budget "
                    f"{qreport['budget']}): {qreport['records']} — "
                    f"masked to reduction identities in aggregates, "
                    f"fill values in per-record features; see "
                    f"JobResult.quarantine for the per-record reasons",
                    RuntimeWarning, stacklevel=2)
        self._result = (self.sink.result(), epoch, self._windows_out,
                        window_edges, live, events, self.pl, qreport)
        return self._result

    def close(self):
        """Release stream, source, and sink — all three, always.

        Safe to call at any point of the lifecycle (including before
        ``start()`` or after a failure inside it) and more than once;
        a close error in one resource never prevents releasing the
        others (the first one re-raises after all three ran, so one
        failed tenant cannot leak wav handles or writer threads into a
        long-lived service process).
        """
        if self._closed:
            return
        self._closed = True
        first: BaseException | None = None
        for release in ((self._stream.close if self._stream is not None
                         else None),
                        self.source.close, self.sink.close):
            if release is None:
                continue
            try:
                release()
            except BaseException as e:   # noqa: BLE001
                first = first or e
        if first is not None:
            raise first


def run_job(m: DatasetManifest, p: DepamParams, specs: list[FeatureSpec],
            source: Source, sink: Sink, mesh: Mesh | None,
            data_axes: tuple[str, ...], pl_: ShardPlan,
            use_kernels: bool, max_steps: int | None,
            options: ExecOptions | None = None,
            window: Window | None = None, instrument=None):
    """Drive the job over plan ``pl_`` to completion; resumable when
    the sink is.

    ``window`` is the job's time resolution: every ``job``-window
    reduction accumulates at it (epoch — one window — when None).
    Returns (features, epoch, windows, window_edges, n_records, events,
    plan, quarantine) — see job.JobResult.  This is the blocking
    single-tenant
    driver: one
    :class:`JobStepper` run start-to-finish, with source/sink released
    in ``finally`` even when binding, sink open, resume validation, or
    any step raises mid-stream.
    """
    stepper = JobStepper(m, p, specs, source, sink, mesh, data_axes, pl_,
                         use_kernels, max_steps, options, window,
                         instrument=instrument)
    return drive(stepper)


def drive(stepper: JobStepper):
    """Run one stepper start-to-finish with guaranteed cleanup."""
    try:
        stepper.start()
        while stepper.step_once():
            pass
        return stepper.finish()
    finally:
        stepper.close()
