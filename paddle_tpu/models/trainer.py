"""Functional training-step factories shared by all model families.

The TPU performance path: ONE jitted XLA program per step (forward +
backward + optimizer sweep), with optional mesh shardings for hybrid
parallel — the capability the reference spreads across its executors,
reducers, and fused optimizer kernels.
"""
from __future__ import annotations

import logging
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as _random
from ..core.autograd import tape_paused
from ..core.tensor import Tensor
from ..nn.layer.layers import _swapped_state, functional_state

__all__ = ["create_train_step", "create_multistep_train_step",
           "create_sharded_train_step", "place_by_spec", "run_steps",
           "restore_training_state", "write_back"]


def place_by_spec(arr, spec, mesh, name=None):
    """device_put ``arr`` with ``spec`` over ``mesh``, replicating instead
    when the spec doesn't divide the array evenly. The fallback is never
    silent: each one is recorded (with a one-line reason) in
    ``profiler.pipeline_stats()["placement_fallbacks"]`` and warned once
    per call site's reason — a renamed/reshaped param that quietly
    de-shards costs HBM and bandwidth, not correctness, so it only
    surfaces through observability."""
    from jax.sharding import NamedSharding

    from ..distributed.spec_layout import default_layout

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ok = True
    bad = None
    for i, s in enumerate(spec):
        if s is None:
            continue
        axes = s if isinstance(s, tuple) else (s,)
        size = int(np.prod([sizes[a] for a in axes]))
        if i >= arr.ndim or arr.shape[i] % size:
            ok = False
            bad = (i, s, size)
    if not ok:
        import warnings

        from .. import profiler
        i, s, size = bad
        reason = (f"place_by_spec: {name or 'array'} shape "
                  f"{tuple(arr.shape)} dim {i} does not divide by "
                  f"{s!r}={size} — replicating (spec was {spec})")
        profiler.record_placement_fallback(reason)
        warnings.warn(reason, RuntimeWarning, stacklevel=2)
        spec = default_layout().replicated()
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _wd_mask(names):
    return {n: ("bias" not in n and "norm" not in n.lower()
                and "ln_" not in n) for n in names}


def _functional_pieces(model, optimizer, loss_fn):
    """Shared setup for the step factories: the functional loss call over
    swapped-in params, the initial trainable/optimizer trees, and the
    weight-decay mask."""
    trainable0 = functional_state(model, trainable_only=True)
    all0 = functional_state(model)
    frozen = {k: v for k, v in all0.items() if k not in trainable0}
    opt_state0 = optimizer.init_state_tree(trainable0)
    wd_mask = _wd_mask(trainable0)

    def loss_call(params, ids, labels, key):
        with _random.key_context(key):
            merged = {**params, **frozen}
            with _swapped_state(model, merged):
                with tape_paused():
                    if loss_fn is not None:
                        out = loss_fn(model, Tensor(ids), Tensor(labels))
                    else:
                        out = model.loss(Tensor(ids), Tensor(labels))
            return out._data

    return loss_call, trainable0, opt_state0, wd_mask


def _protective_copies(donate, trainable0, opt_state0):
    """Copies handed back under plain donation: trainable0 aliases the
    model's live parameter buffers, and donating those would delete the
    model's own weights on the first step (use-after-free on any later
    model(...) call). donate="consume" skips this deliberately."""
    if donate and donate != "consume":
        trainable0 = {k: jnp.copy(v) for k, v in trainable0.items()}
        opt_state0 = jax.tree_util.tree_map(jnp.copy, opt_state0)
    return trainable0, opt_state0


def create_train_step(model, optimizer, loss_fn=None, donate=False):
    """(params, opt_state, key, ids, labels, lr) -> (loss, params, opt_state).
    ``model.loss(ids, labels)`` is used unless ``loss_fn(model, ids, labels)``
    is given.

    ``donate=True`` donates the params/opt-state buffers to XLA
    (input-output aliasing): the update writes in place instead of
    allocating a second copy of every parameter and moment, freeing
    ~3x params bytes of HBM for bigger batches. The caller must then
    treat the passed-in trees as consumed (use the returned ones).

    ``donate="consume"`` additionally skips the protective copies of the
    returned trees — the returned params ALIAS the model's live weight
    buffers, so the first step invalidates the stateful model. One-shot
    benchmark/training-loop use only; it removes the transient 1x-params
    + 1x-moments copy that pushes billion-param models past HBM at
    setup time."""
    _loss_call, trainable0, opt_state0, wd_mask = _functional_pieces(
        model, optimizer, loss_fn)

    def train_step(params, opt_state, key, ids, labels, lr):
        loss, grads = jax.value_and_grad(
            lambda p: _loss_call(p, ids, labels, key))(params)
        # with value_and_grad's jvp()/transpose(jvp()) this makes forward,
        # backward and optimizer three disjoint prefixes of every op's name
        with jax.named_scope("optimizer"):
            new_params, new_opt_state = optimizer.apply_gradients(
                params, grads, opt_state, lr, wd_mask=wd_mask)
        return loss, new_params, new_opt_state

    train_step = jax.jit(train_step,
                         donate_argnums=(0, 1) if donate else ())
    trainable0, opt_state0 = _protective_copies(donate, trainable0,
                                                opt_state0)
    return train_step, trainable0, opt_state0


def create_multistep_train_step(model, optimizer, loss_fn=None,
                                donate=False, steps=8, accumulate=1):
    """``steps`` optimizer steps inside ONE jitted program via
    ``lax.scan`` — the production-JAX training-loop shape: the host
    dispatches once per K steps, so per-execute dispatch cost (python
    loop overhead plus the runtime's launch latency) amortizes to
    dispatch/K and the device runs back-to-back.

    Returns ``(step_K, params0, opt_state0)`` where
    ``step_K(params, opt_state, key, ids, labels, lr)`` takes stacked
    batches ``ids, labels: [K, B, S]`` and returns
    ``(losses[K], params, opt_state)``. Per-step RNG is
    ``fold_in(key, i)``, matching ``create_train_step`` semantics for
    the same fold sequence. ``donate`` as in ``create_train_step``.

    ``accumulate=M`` > 1 turns each scan step into M gradient-
    accumulation microbatches (inputs stacked to [K, M, B, S]): grads
    sum in f32 and average before one optimizer apply — the functional
    analog of the fleet stack's ``accumulate_steps``, for effective
    batches that don't fit HBM in one forward. Per-microbatch RNG is
    ``fold_in(key, i * M + j)``; the returned per-step loss is the
    microbatch mean."""
    _loss_call, trainable0, opt_state0, wd_mask = _functional_pieces(
        model, optimizer, loss_fn)

    def step_k(params, opt_state, key, ids, labels, lr):
        if ids.shape[0] != steps:
            # scan would silently run ids.shape[0] optimizer steps, not
            # the K the caller sized schedules/logging around — catch the
            # mis-stacked input at trace time (mirrors the accumulate
            # check below)
            raise ValueError(
                f"steps={steps} expects inputs stacked [{steps}, "
                f"batch, ...]; got leading dim {ids.shape[0]} in "
                f"{tuple(ids.shape)}")
        if accumulate > 1 and ids.shape[1] != accumulate:
            # the fori_loop index lowers to dynamic_slice, whose OOB
            # clamping would silently repeat the last microbatch — catch
            # the mis-stacked input at trace time instead
            raise ValueError(
                f"accumulate={accumulate} expects inputs stacked "
                f"[steps, {accumulate}, batch, ...]; got microbatch dim "
                f"{ids.shape[1]} in {tuple(ids.shape)}")

        def body(carry, xs):
            p, s = carry
            i, ids_i, labels_i = xs
            if accumulate == 1:
                loss, grads = jax.value_and_grad(
                    lambda q: _loss_call(q, ids_i, labels_i,
                                         jax.random.fold_in(key, i)))(p)
            else:
                def micro(j, acc):
                    gsum, lsum = acc
                    lj, gj = jax.value_and_grad(
                        lambda q: _loss_call(
                            q, ids_i[j], labels_i[j],
                            jax.random.fold_in(key, i * accumulate + j))
                    )(p)
                    gsum = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), gsum, gj)
                    return gsum, lsum + lj
                zeros = jax.tree_util.tree_map(
                    lambda v: jnp.zeros(v.shape, jnp.float32), p)
                gsum, lsum = jax.lax.fori_loop(
                    0, accumulate, micro,
                    (zeros, jnp.zeros((), jnp.float32)))
                grads = jax.tree_util.tree_map(
                    lambda g: g / accumulate, gsum)
                loss = lsum / accumulate
            with jax.named_scope("optimizer"):
                p, s = optimizer.apply_gradients(p, grads, s, lr,
                                                 wd_mask=wd_mask)
            return (p, s), loss
        n = ids.shape[0]
        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state),
            (jnp.arange(n), ids, labels))
        return losses, params, opt_state

    step_k = jax.jit(step_k, donate_argnums=(0, 1) if donate else ())
    trainable0, opt_state0 = _protective_copies(donate, trainable0,
                                                opt_state0)
    return step_k, trainable0, opt_state0


def create_sharded_train_step(model, optimizer, mesh, param_spec_fn,
                              data_axis: str = "dp", loss_fn=None,
                              donate=False, steps=None, accumulate=1):
    """Hybrid-parallel variant: params/opt-state laid out by
    ``param_spec_fn(name) -> PartitionSpec`` over ``mesh``; batch sharded
    over ``data_axis``. Returns (step, params, opt_state, shard_batch).
    ``donate=True`` aliases params/opt-state in place (see
    create_train_step) — treat the passed-in trees as consumed.
    ``steps=K`` wraps the scan-of-K trainer instead (ids/labels stacked
    to [K, B, ...]; ``shard_batch`` then shards dim 1, the per-step
    batch, over ``data_axis``). ``accumulate=M`` composes with steps
    (inputs [K, M, B, ...]; the batch moves to dim 2 and shard_batch
    follows it)."""
    from jax.sharding import NamedSharding

    from ..distributed.spec_layout import SpecLayout

    layout = SpecLayout(data_axis=data_axis)
    if steps:
        step, params, opt_state = create_multistep_train_step(
            model, optimizer, loss_fn, donate=donate, steps=steps,
            accumulate=accumulate)
    else:
        if accumulate != 1:
            raise ValueError("accumulate requires steps=K (the scan "
                             "trainer owns the microbatch loop)")
        step, params, opt_state = create_train_step(
            model, optimizer, loss_fn, donate=donate)

    def place(name, arr):
        return place_by_spec(arr, param_spec_fn(name), mesh, name=name)

    params = {k: place(k, v) for k, v in params.items()}
    new_state = {}
    for k, st in opt_state.items():
        new_state[k] = {
            n: (jax.device_put(v, NamedSharding(mesh,
                                                layout.replicated()))
                if v.ndim == 0 else place(k, v))
            for n, v in st.items()}
    opt_state = new_state

    def shard_batch(arr):
        arr = jnp.asarray(arr)
        # batch dim over the data axis, rest replicated — spec trimmed to
        # the array's rank (labels are often rank-1). With steps=K the
        # leading dim is the scan axis and the per-step batch is dim 1;
        # with accumulate=M the microbatch axis sits at dim 1 and the
        # batch moves to dim 2. Arrays too small to carry a batch dim
        # (per-step scalars/vectors) stay replicated.
        if steps:
            batch_dim = 2 if accumulate > 1 else 1
            if arr.ndim <= batch_dim:
                spec = layout.replicated()
            else:
                spec = layout.stacked_batch(arr.ndim,
                                            batch_dim=batch_dim)
        else:
            spec = layout.batch(arr.ndim)
        return jax.device_put(arr, NamedSharding(mesh, spec))

    def sharded_step(params, opt_state, key, ids, labels, lr):
        # the ambient mesh is how ops that must partition themselves
        # (the Mosaic flash kernel: ops/pallas/flash_attention._per_shard)
        # learn the axis names and sizes at trace time
        with jax.set_mesh(mesh):
            return step(params, opt_state, key, ids, labels, lr)

    # the jitted program itself, for lowering/inspection under the mesh
    sharded_step.jitted = step
    return sharded_step, params, opt_state, shard_batch


def _recoverable_fault_types():
    """Exceptions ``run_steps(on_fault=)`` treats as recoverable faults:
    the comm watchdog's deadline abort and the fault harness's injected
    worker death. Lazy — the distributed package only loads when a fault
    handler is installed."""
    from ..distributed.comm_watchdog import CommTimeoutError
    from ..distributed.resilience.faults import InjectedCrash
    return (CommTimeoutError, InjectedCrash)


def restore_training_state(checkpoint_manager, params, opt_state):
    """Resolve the newest committed checkpoint and load it over copies of
    the given training trees — each leaf keeps its CURRENT sharding, so a
    relaunched (possibly shrunk) world reshards on restore. Returns
    ``(params, opt_state, step)`` where ``step`` is the committed step the
    trees now hold, or ``None`` when no committed checkpoint exists.

    This is the restore half of the ``run_steps`` checkpoint layout
    (``{"params": ..., "opt_state": ..., "step": ...}``); a typical
    ``on_fault`` handler is::

        def on_fault(exc, step):
            got = restore_training_state(manager, params0, opt_state0)
            if got is None:
                return None          # nothing committed: re-raise
            p, s, committed = got
            return p, s, committed + 1
    """
    state = {"params": dict(params),
             "opt_state": {k: dict(v) for k, v in opt_state.items()},
             "step": -1}
    step = checkpoint_manager.restore(state)
    if step is None:
        return None

    def unwrap(v):
        return v._data if isinstance(v, Tensor) else v

    params = {k: unwrap(v) for k, v in state["params"].items()}
    opt_state = {k: {n: unwrap(v) for n, v in st.items()}
                 for k, st in state["opt_state"].items()}
    return params, opt_state, step


_log = logging.getLogger("paddle_tpu.train")


def _own(x, y):
    """Seconds from mark ``x`` to mark ``y`` less the collector's pauses
    between them."""
    return (y[0] - x[0]) * 1e-9 - (y[1] - x[1])


class _LoopRecord:
    """``run_steps``' record of its own iterations, kept in the loop's
    ``PipelineMetrics``. A mark is ``(time.time_ns(), gc pause seconds so
    far, generation-2 collections so far)``: ``time.time_ns`` is the
    profiler's clock (an xplane's ``profile_start_time`` + an event's
    ``start_ns``), so a record's stamps sit on a profile's timeline.

    An iteration's marks ``(a, b, c, d, e, f, g)`` open feed_wait, end it,
    open dispatch, end it (= open checkpoint), end the checkpoint (= open
    the fetch), end the fetch's wait on the device (= open the caller's
    on_log) and end that; the iteration runs from the last mark of the
    one before to ``g``. Each phase leaves out the collector's pauses,
    which are the phase ``gc``; ``rest`` is the loop's own Python between
    the phases."""

    SLOW_RING = 32      # the slow check's median is over these last...
    SLOW_MIN = 4        # ...once there are this many
    SLOW_MS = 100.0     # and an iteration is slow by this much over it

    def __init__(self, metrics):
        from ..profiler.tracing import trace_span
        self.metrics = metrics
        self._trace_span = trace_span
        # seconds paused, collections, generation-2 collections since the
        # call began: written by the collector's callback alone, which may
        # take no lock (a collection can start while one is held)
        self._collected = [0.0, 0, 0]
        self._gc_taken = (0.0, 0, 0)
        self._gc_open = None
        self.prev = None        # the last iteration's marks
        self._ring = []
        self._median = None
        self._seen = 0

    def mark(self):
        acc = self._collected
        return time.time_ns(), acc[0], acc[2]

    def on_gc(self, phase, info):
        """A ``gc.callbacks`` entry: the pause, and a ``gc::collect``
        span on the collecting thread."""
        if phase == "start":
            span = self._trace_span("gc::collect", cat="gc",
                                    generation=info["generation"])
            self._gc_open = (span, time.perf_counter())
            return
        if self._gc_open is None:   # began before the callback was added
            return
        span, t0 = self._gc_open
        self._gc_open = None
        pause = time.perf_counter() - t0
        span.set(collected=info["collected"])
        span.end()
        acc = self._collected
        acc[0] += pause
        acc[1] += 1
        if info["generation"] == 2:
            acc[2] += 1

    def take_gc(self):
        """The collector's ``(pause_s, collections, gen2)`` since the last
        call."""
        now, was = tuple(self._collected), self._gc_taken
        self._gc_taken = now
        return tuple(x - y for x, y in zip(now, was))

    def iteration(self, step, marks, dry, overran, ready):
        """One whole iteration: dispatch(step) and fetch(step - 1).
        ``dry``: the previous step was done before this one was launched;
        ``overran``: it was done right after the launch, not before;
        ``ready``: this step's ``is_ready`` (asked only of a slow
        iteration, at its end: done already means the device stands idle
        while the host is still behind, not done that the device itself
        took the time)."""
        start = self.prev[-1]
        a, b, c, d, e, f, g = marks
        ms = {"feed_wait": _own(a, b), "dispatch": _own(c, d),
              "checkpoint": _own(d, e), "fetch_wait": _own(e, f),
              "callback": _own(f, g), "gc": g[1] - start[1],
              "rest": _own(start, a) + _own(b, c)}
        cause = "dispatch" if overran else None
        if dry:
            # the largest host phase since the previous launch ended; the
            # fetch's wait is the device's time, not the host's
            pd, pe, pf, pg = self.prev[3:]
            since = {"feed_wait": ms["feed_wait"],
                     "callback": _own(pf, pg), "gc": c[1] - pd[1],
                     "checkpoint": _own(pd, pe),
                     "rest": _own(pg, a) + _own(b, c)}
            cause = max(since, key=since.get)
        self.prev = marks
        loop_ms = ((g[0] - start[0]) * 1e-9 - ms["callback"]) * 1e3
        record = {"step": step, "loop_ms": loop_ms,
                  "t_ns": {"start": start[0], "feed_wait": a[0],
                           "dispatch": c[0], "checkpoint": d[0],
                           "fetch_wait": e[0], "callback": f[0],
                           "end": g[0]},
                  "ms": {k: v * 1e3 for k, v in ms.items()},
                  "gc_gen2": g[2] - start[2], "starved": cause}
        self.metrics.add_iteration(
            record, (d[0] - c[0]) * 1e-9 if dry else 0.0, self.take_gc())
        self._check_slow(record, ready)

    def _check_slow(self, record, ready):
        """One WARNING line for an iteration over twice the median of the
        last ``SLOW_RING`` and ``SLOW_MS`` over it. The ring is sorted only
        when it wraps or when an iteration passes twice the last median."""
        ms, ring = record["loop_ms"], self._ring
        if len(ring) >= self.SLOW_MIN and (
                self._median is None or self._seen % self.SLOW_RING == 0
                or ms > 2 * self._median):
            self._median = med = statistics.median(ring)
            if ms > 2 * med and ms - med >= self.SLOW_MS:
                _log.warning(
                    "run_steps: step %d took %.1f ms, the median of the "
                    "last %d %.1f ms: %s ms; generation-2 collections %d; "
                    "the device ran dry before the launch: %s; idle at the "
                    "end: %s", record["step"], ms, len(ring), med,
                    ", ".join(f"{k} {v:.1f}"
                              for k, v in record["ms"].items()),
                    record["gc_gen2"], record["starved"] or "no",
                    "unknown" if ready is None else
                    "yes" if ready() else "no")
        if len(ring) < self.SLOW_RING:
            ring.append(ms)
        else:
            ring[self._seen % self.SLOW_RING] = ms
        self._seen += 1


def run_steps(step, params, opt_state, feed, *, key=None, lr=1e-3,
              log_every=0, on_log=None, name=None, start_step=0,
              checkpoint_manager=None, on_fault=None):
    """Overlap-aware loop runner: drive ``step`` over every ``(ids,
    labels)`` batch in ``feed`` WITHOUT ever blocking on the current
    step's loss. JAX dispatch is async — the returned loss is a future —
    so metrics are fetched one step behind: while the device runs step
    ``i``, the host ``device_get``s step ``i-1``'s loss and pulls batch
    ``i+1``. With ``feed`` wrapped in ``io.prefetch_to_device``, host
    batch prep, H2D transfer, and device compute fully overlap.

    ``step`` is a ``create_train_step``/``create_multistep_train_step``/
    ``create_sharded_train_step`` product; per-step RNG is
    ``fold_in(key, i)``, matching the synchronous loop those factories
    document. ``lr`` is a float or a ``callable(i) -> float`` schedule.
    ``log_every=N`` calls ``on_log(step_index, fetched_loss)`` every N
    fetched steps (the index lags the dispatched step by one — async
    logging, never a sync point beyond the lagged fetch).

    Returns ``(params, opt_state, losses)`` — ``losses`` holds every
    fetched per-step metric in order (scalars for the single-step
    trainer, ``[K]`` arrays for the multistep one).

    Wait-time accounting lands in ``profiler.pipeline_stats()``: time
    blocked on ``feed`` counts as host_blocked (input-bound), time
    blocked inside the lagged ``device_get`` as device_blocked
    (compute-bound), and the host's own time inside the call of ``step``
    (key fold, schedule, dispatch) as dispatch_s. When ``feed`` is a ``DevicePrefetcher`` its own
    metrics object is reused (one snapshot answers for the whole
    pipeline); otherwise a fresh source named ``name`` (default
    ``"run_steps"``) is registered for the duration of the run.

    The loop also records itself there, always on. Each whole iteration
    (feed_wait, dispatch and checkpoint of step ``i``, fetch of ``i-1``)
    is stamped with ``time.time_ns()``, the profiler's clock, into a
    record: ``step``, ``loop_ms`` (the iteration less the caller's
    ``on_log``), ``t_ns`` (where each phase began, and ``end``), ``ms``
    (feed_wait, dispatch, checkpoint, fetch_wait, callback, gc, rest),
    ``gc_gen2`` and ``starved``. ``loop_ms`` goes to a histogram, the
    three longest records to ``slowest``. Right before and after each
    launch the loop asks the previous loss ``is_ready()``: done before
    the launch, the device had nothing queued, so it waited on the host
    (``starved_steps``, ``starved_s``, and ``starved_by`` the largest
    host phase since the previous launch: feed_wait, callback, gc,
    checkpoint or rest; done only after it, ``dispatch``); the last
    eight such records are kept in ``starved``. For the call's length a
    ``gc.callbacks`` entry counts Python's collections (``gc_pause_s``,
    ``gc_collections``, ``gc_gen2``) and spans each as ``gc::collect``.
    An iteration over twice the median of the last 32 and 100 ms over
    it logs one WARNING on ``logging.getLogger("paddle_tpu.train")``
    with its phases.

    Preemption tolerance (``distributed.resilience``): with
    ``checkpoint_manager=`` the loop calls ``maybe_save(i, state)``
    after dispatching step ``i`` with the post-step trees under
    ``{"params", "opt_state", "step"}`` — an async manager blocks only
    for the device→host snapshot; every disk write happens behind. With
    ``on_fault=`` a ``CommTimeoutError`` (watchdog deadline: a peer died
    mid-collective) or ``InjectedCrash`` (fault harness) is caught and
    ``on_fault(exc, step_index)`` decides: return ``None`` to re-raise,
    or ``(params, opt_state, resume_step)`` (usually via
    ``restore_training_state``) to resume — losses past ``resume_step``
    are discarded and the feed replays from there, so the trajectory is
    exactly what an unkilled run restored from the same checkpoint
    produces (per-step RNG is ``fold_in(key, i)``, a function of the
    global step). Recovery needs a replayable feed: pass a *callable*
    ``feed(start) -> iterable`` yielding batches for steps ``start,
    start+1, ...``; ``start_step`` offsets the whole run (resuming a
    previous process at the step after its restored checkpoint).
    """
    import gc

    from ..io.prefetch import DevicePrefetcher, PipelineMetrics
    from ..profiler import tracing

    if key is None:
        key = jax.random.key(0)
    lr_fn = lr if callable(lr) else (lambda i: lr)

    feed_is_factory = callable(feed) and not hasattr(feed, "__iter__")
    if on_fault is not None and not feed_is_factory:
        # fail at call time, not after the first fault has already paid
        # for a full checkpoint restore it can't use
        raise TypeError(
            "run_steps fault recovery needs a replayable feed: pass "
            "feed as a callable feed(start) -> iterable of batches")
    owns_metrics = not isinstance(feed, DevicePrefetcher)
    if owns_metrics:
        from .. import profiler
        metrics = PipelineMetrics(name or "run_steps")
        profiler.register_pipeline_source(metrics.name, metrics)
    else:
        metrics = feed.metrics
    recoverable = _recoverable_fault_types() if on_fault is not None \
        else ()

    losses = []
    pending = None
    loop = _LoopRecord(metrics)
    mark = loop.mark

    def fetch(val, i, start):
        # -> the marks where the wait for the device and the caller's
        # on_log end
        with tracing.trace_span("train::fetch", cat="train", step=i):
            got = jax.device_get(val)
        waited = mark()
        metrics.add_time("device_blocked_s", (waited[0] - start[0]) * 1e-9)
        losses.append(got)
        if not (log_every and on_log is not None and i % log_every == 0):
            return waited, waited
        with tracing.trace_span("train::callback", cat="train", step=i):
            on_log(i, got)
        called = mark()
        metrics.add_time("callback_s", (called[0] - waited[0]) * 1e-9)
        return waited, called

    i0 = start_step
    on_gc = loop.on_gc
    gc.callbacks.append(on_gc)
    try:
        it = iter(feed(i0) if feed_is_factory else feed)
        i = i0
        while True:
            try:
                a = mark()
                # span handle, not a with-block: a StopIteration break
                # drops it unrecorded instead of logging a bogus wait
                feed_span = tracing.trace_span("train::feed_wait",
                                               cat="train", step=i)
                try:
                    batch = next(it)
                except StopIteration:
                    feed_span.drop()
                    break
                feed_span.end()
                b = mark()
                if owns_metrics:
                    metrics.add_time("host_blocked_s", (b[0] - a[0]) * 1e-9)
                    metrics.inc("batches_out")
                ids, labels = batch
                # the previous step already done before this one is
                # launched: the device has nothing queued, it waits on
                # the host (is_ready does not block)
                ready = getattr(pending, "is_ready", None)
                dry = ready is not None and ready()
                # dispatch_s: the host's own work a step (the key fold,
                # the schedule, flattening the trees, the launches),
                # always on like host_blocked_s and device_blocked_s
                c = mark()
                with tracing.trace_step("train::dispatch", i, cat="train"):
                    loss, params, opt_state = step(
                        params, opt_state, jax.random.fold_in(key, i),
                        ids, labels, lr_fn(i))
                d = mark()
                metrics.add_time("dispatch_s", (d[0] - c[0]) * 1e-9)
                # done now and not before: the launch itself outran the
                # device
                overran = ready is not None and not dry and ready()
                e = d
                if checkpoint_manager is not None:
                    with tracing.trace_span("train::checkpoint", cat="train",
                                            step=i):
                        checkpoint_manager.maybe_save(
                            i, {"params": params, "opt_state": opt_state,
                                "step": i})
                    e = mark()
                if pending is not None:
                    f, g = fetch(pending, i - 1, e)
                    loop.iteration(i, (a, b, c, d, e, f, g), dry, overran,
                                   getattr(loss, "is_ready", None))
                else:       # no fetch: not a whole iteration
                    loop.prev = (a, b, c, d, e, e, e)
                pending = loss
                i += 1
            except recoverable as exc:
                recovered = on_fault(exc, i)
                if recovered is None:
                    raise
                params, opt_state, resume = recovered
                if pending is not None and i - 1 < resume:
                    # the lagged loss of step i-1 is BEFORE the resume
                    # point: part of the kept trajectory, fetch it (its
                    # step completed; the fault hit a later boundary)
                    fetch(pending, i - 1, mark())
                del losses[max(0, resume - i0):]
                pending = None
                loop.prev = None
                i = int(resume)
                it = iter(feed(i))
                if checkpoint_manager is not None:
                    checkpoint_manager.record_restart()
        if pending is not None:
            fetch(pending, i - 1, mark())
    finally:
        gc.callbacks.remove(on_gc)
        metrics.add_gc(loop.take_gc())
        if owns_metrics:
            from .. import profiler
            profiler.unregister_pipeline_source(metrics.name, metrics)
    return params, opt_state, losses


def write_back(model, params, strict=False):
    """Write functional params back into the stateful layer.

    Params whose names aren't on the model are NOT silently dropped: a
    sharded-rename bug (e.g. a spec_fn keyed to old names) would
    otherwise train a tree the model never sees. Unknown names warn by
    default and raise ``KeyError`` with ``strict=True``."""
    entries = dict(model.named_parameters())
    unknown = [k for k in params if k not in entries]
    if unknown:
        msg = (f"write_back: {len(unknown)} param(s) not on the model, "
               f"dropped: {sorted(unknown)[:5]}"
               f"{'...' if len(unknown) > 5 else ''}")
        if strict:
            raise KeyError(msg)
        import warnings
        warnings.warn(msg, RuntimeWarning, stacklevel=2)
    for k, v in params.items():
        if k in entries:
            entries[k]._data = v
