"""Async device-feed pipeline tests (ISSUE 3): io.prefetch_to_device,
trainer.run_steps, profiler.pipeline_stats, place_by_spec fallback
visibility. Oracles: the async pipeline must be the SAME math as the
synchronous loop (ordering determinism + loss parity), with the overlap
machinery observable through the profiler registry."""
import gc
import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.io import DevicePrefetcher, prefetch_to_device
from paddle_tpu.models import (GPTForCausalLM, create_multistep_train_step,
                               create_train_step, gpt2_tiny, place_by_spec,
                               run_steps)

RNG = np.random.RandomState(0)


def _batches(n, batch=2, seq=8):
    """Deterministic numbered (ids, labels) batches: batch i is filled
    with value i so ordering is checkable from the payload."""
    return [(np.full((batch, seq), i, np.int32),
             np.full((batch, seq), i, np.int32)) for i in range(n)]


@pytest.fixture(scope="module")
def gpt_step():
    """One compiled tiny-GPT train step shared by the runner tests (the
    jit compile dominates; nothing here mutates the initial trees — no
    donation, every call returns fresh ones)."""
    paddle.seed(3)
    m = GPTForCausalLM(gpt2_tiny())
    m.eval()
    opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
    step, params, opt_state = create_train_step(m, opt)
    # compile once here (jit is lazy) so no single test absorbs it
    step(params, opt_state, jax.random.key(0),
         np.zeros((2, 8), np.int32), np.zeros((2, 8), np.int32), 0.0)
    return step, params, opt_state


class TestPrefetcher:
    def test_ordering_deterministic_and_on_device(self):
        data = _batches(20)
        with prefetch_to_device(iter(data), depth=3,
                                name="t_order") as pf:
            out = list(pf)
        assert len(out) == 20
        for i, (x, y) in enumerate(out):
            assert isinstance(x, jax.Array) and isinstance(y, jax.Array)
            assert int(x[0, 0]) == i and int(y[0, 0]) == i

    def test_stack_layout_feeds_multistep_trainer(self):
        """stack=K must emit the [K, B, ...] layout that
        create_multistep_train_step(steps=K) validates at trace time —
        and a ragged tail (< K source batches) is dropped."""
        K = 3
        data = _batches(7)   # 7 = 2 full stacks + ragged 1
        with prefetch_to_device(iter(data), depth=2, stack=K,
                                name="t_stack") as pf:
            stacks = list(pf)
        assert len(stacks) == 2
        assert all(tuple(x.shape) == (K, 2, 8) for x, _ in stacks)
        # batch i of stack s carries value s*K+i: order survived stacking
        assert [int(v) for v in stacks[1][0][:, 0, 0]] == [3, 4, 5]

        paddle.seed(11)
        m = GPTForCausalLM(gpt2_tiny())
        m.eval()
        opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
        step_k, p, s = create_multistep_train_step(m, opt, steps=K)
        # the trace-time steps check accepts the stacked layout and
        # scans K losses (eval_shape: full trace incl. the validation,
        # no XLA compile — keeps this inside the tier-1 budget)
        losses, _, _ = jax.eval_shape(step_k, p, s, jax.random.key(0),
                                      stacks[0][0], stacks[0][1], 1e-3)
        assert losses.shape == (K,)
        # and an un-stacked batch is rejected by the same check
        with pytest.raises(ValueError, match=f"steps={K}"):
            jax.eval_shape(step_k, p, s, jax.random.key(0),
                           data[0][0], data[0][1], 1e-3)

    @pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
    def test_mesh_sharded_placement(self):
        """sharding= takes a NamedSharding or the shard_batch-style
        callable from create_sharded_train_step: either way batches land
        distributed over the data axis."""
        from jax.sharding import Mesh, NamedSharding

        from paddle_tpu.distributed import default_layout

        layout = default_layout()
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("dp", "tp"))
        data = [(np.zeros((4, 8), np.int32), np.zeros((4, 8), np.int32))]

        sh = NamedSharding(mesh, layout.batch())
        with prefetch_to_device(iter(data), sharding=sh,
                                name="t_mesh1") as pf:
            x, _ = next(iter(pf))
        assert x.sharding.spec == layout.batch()
        assert len(x.addressable_shards) == 8
        assert x.addressable_shards[0].data.shape[0] == 2   # 4 / dp=2

        def shard_batch(a):
            a = jnp.asarray(a)
            return jax.device_put(
                a, NamedSharding(mesh, layout.batch(a.ndim)))

        with prefetch_to_device(iter(data), sharding=shard_batch,
                                name="t_mesh2") as pf:
            x, _ = next(iter(pf))
        assert x.sharding.spec[0] == "dp"

    def test_clean_shutdown_mid_epoch(self):
        """close() mid-iteration stops the producer promptly — no hang,
        no exception, thread joined."""
        produced = []

        def endless():
            i = 0
            while True:
                produced.append(i)
                yield (np.full((2, 4), i, np.int32),
                       np.full((2, 4), i, np.int32))
                i += 1

        pf = prefetch_to_device(endless(), depth=2, name="t_shutdown")
        it = iter(pf)
        for _ in range(3):
            next(it)
        pf.close()
        assert not pf._thread.is_alive()
        n_after_close = len(produced)
        time.sleep(0.1)
        assert len(produced) == n_after_close   # really stopped

    def test_close_unblocks_waiting_consumer_promptly(self):
        """A consumer blocked on an empty queue must get StopIteration
        quickly when another thread close()s — not a TimeoutError after
        the full timeout (code-review finding on the first cut)."""
        release = threading.Event()

        def slow_source():
            # long enough that the consumer is provably blocked, short
            # enough that close()'s thread-join doesn't stall the tier-1
            # budget (a blocked next(source) can't be interrupted, only
            # waited out)
            release.wait(1.5)
            yield _batches(1)[0]

        pf = prefetch_to_device(slow_source(), name="t_close_wait")
        outcome = []

        def consume():
            t0 = time.perf_counter()
            try:
                next(iter(pf))
                outcome.append(("item", time.perf_counter() - t0))
            except StopIteration:
                outcome.append(("stop", time.perf_counter() - t0))
            except TimeoutError:
                outcome.append(("timeout", time.perf_counter() - t0))

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        time.sleep(0.1)   # let the consumer block on the empty queue
        pf.close()
        t.join(5.0)
        release.set()
        assert outcome and outcome[0][0] == "stop", outcome
        assert outcome[0][1] < 2.0   # promptly, not the 120 s timeout
        # and iterating a closed prefetcher stays terminated
        with pytest.raises(StopIteration):
            next(iter(pf))

    def test_producer_exception_propagates(self):
        def bad():
            yield _batches(1)[0]
            raise RuntimeError("synthetic decode failure")

        with prefetch_to_device(bad(), name="t_exc") as pf:
            it = iter(pf)
            next(it)
            with pytest.raises(RuntimeError, match="synthetic decode"):
                next(it)
            assert pf.metrics.snapshot()["producer_exceptions"] == 1

    def test_backpressure_bounds_producer_lead(self):
        """depth=2: a slow consumer must hold the producer to a bounded
        lead (queue + at most one placed batch in hand + one generator
        step) — prefetch is N-deep buffering, not unbounded slurping."""
        produced = []

        def source():
            for i in range(30):
                produced.append(i)
                yield (np.full((2, 4), i, np.int32),
                       np.full((2, 4), i, np.int32))

        depth = 2
        max_lead = 0
        with prefetch_to_device(source(), depth=depth,
                                name="t_bp") as pf:
            it = iter(pf)
            for consumed in range(1, 9):
                next(it)
                time.sleep(0.02)   # slow consumer
                max_lead = max(max_lead, len(produced) - consumed)
            snap = pf.metrics.snapshot()
        assert max_lead <= depth + 2, max_lead
        assert snap["producer_blocked_s"] > 0.0   # backpressure engaged

    def test_depth_validated(self):
        with pytest.raises(ValueError, match="depth"):
            DevicePrefetcher(iter([]), depth=0)
        with pytest.raises(ValueError, match="stack"):
            DevicePrefetcher(iter([]), stack=0)


class TestRunSteps:
    def test_matches_synchronous_loop(self, gpt_step):
        """run_steps (lagged fetch, prefetched feed) == the documented
        synchronous loop on the same fold sequence: identical losses,
        identical final params."""
        step, params, opt_state = gpt_step
        key = jax.random.key(7)
        data = [(RNG.randint(0, 256, (2, 8)).astype(np.int32),
                 RNG.randint(0, 256, (2, 8)).astype(np.int32))
                for _ in range(6)]

        p, s = params, opt_state
        ref = []
        for i, (x, y) in enumerate(data):
            loss, p, s = step(p, s, jax.random.fold_in(key, i), x, y, 5e-3)
            ref.append(float(loss))

        with prefetch_to_device(iter(data), depth=2, name="t_rs") as pf:
            p2, s2, losses = run_steps(step, params, opt_state, pf,
                                       key=key, lr=5e-3)
        np.testing.assert_allclose([float(l) for l in losses], ref,
                                   rtol=1e-6)
        k = next(iter(p))
        np.testing.assert_allclose(np.asarray(p2[k]), np.asarray(p[k]),
                                   rtol=1e-6)

    def test_log_every_is_lagged_and_complete(self, gpt_step):
        step, params, opt_state = gpt_step
        data = _batches(5)
        seen = []
        with prefetch_to_device(iter(data), name="t_log") as pf:
            _, _, losses = run_steps(
                step, params, opt_state, pf, key=jax.random.key(0),
                lr=1e-3, log_every=2,
                on_log=lambda i, v: seen.append((i, float(v))))
        assert [i for i, _ in seen] == [0, 2, 4]
        assert len(losses) == 5
        for i, v in seen:
            assert v == float(losses[i])

    def test_lr_schedule_callable(self, gpt_step):
        step, params, opt_state = gpt_step
        lrs = []
        _, _, losses = run_steps(
            step, params, opt_state, _batches(3),
            key=jax.random.key(0),
            lr=lambda i: lrs.append(i) or 1e-3)
        assert lrs == [0, 1, 2] and len(losses) == 3

    def test_plain_iterable_registers_own_source(self, gpt_step):
        """A bare list feed gets its own pipeline source for the duration
        of the run (sampled via the on_log hook), unregistered after."""
        step, params, opt_state = gpt_step
        during = []
        run_steps(step, params, opt_state, _batches(3),
                  key=jax.random.key(0), lr=1e-3, log_every=1,
                  on_log=lambda i, v: during.append(
                      "run_steps" in profiler.pipeline_stats()))
        assert during and all(during)
        assert "run_steps" not in profiler.pipeline_stats()


class _Loss:
    """A loss whose readiness the test decides: done from the start
    (``ready=True``: by the next launch the device has run dry), or done
    only once ``device_get`` asks for it (no launch ever finds it done)."""

    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready

    def copy_to_host_async(self):       # jax.device_get calls it first
        self.ready = True


def _stub_run(monkeypatch, n=10, ready=True, on_log=None, feed_sleep=None):
    """``run_steps`` over a step that launches nothing (it returns a
    ``_Loss``) and a plain feed; ``feed_sleep=(i, s)`` sleeps before batch
    i. Returns the loop's own source's last snapshot."""
    def step(params, opt_state, key, ids, labels, lr):
        return _Loss(ready), params, opt_state

    def feed():
        for i, b in enumerate(_batches(n)):
            if feed_sleep and i == feed_sleep[0]:
                time.sleep(feed_sleep[1])
            yield b

    kept = {}
    orig = profiler.unregister_pipeline_source

    def keep(name, metrics=None):
        kept["snap"] = metrics.snapshot()
        orig(name, metrics)
    monkeypatch.setattr(profiler, "unregister_pipeline_source", keep)
    run_steps(step, {}, {}, feed(), log_every=1 if on_log else 0,
              on_log=on_log)
    return kept["snap"]


def _record(snap, step):
    return next(r for r in snap["starved"] + snap["slowest"]
                if r["step"] == step)


def _sleep_on(at, s):
    return lambda i, loss: time.sleep(s) if i == at else None


def _check_callback(snap, logged):
    # on_log(5) runs in iteration 6 (dispatch 6, fetch 5): its time is the
    # record's callback and no part of loop_ms; dispatch 7 found the
    # device dry because of it
    assert _record(snap, 6)["ms"]["callback"] >= 45
    assert snap["loop_ms"]["max"] < 45 and snap["callback_s"] >= 0.045
    assert _record(snap, 7)["starved"] == "callback"
    assert snap["starved_by"]["callback"] >= 1


def _check_feed(snap, logged):
    rec = _record(snap, 5)
    assert rec["ms"]["feed_wait"] >= 45 and rec["loop_ms"] >= 45
    assert rec["starved"] == "feed_wait"
    assert snap["starved_by"]["feed_wait"] >= 1


def _check_never_ready(snap, logged):
    assert snap["starved_steps"] == 0 and snap["starved_by"] == {}
    assert snap["starved"] == [] and snap["starved_s"] == 0.0
    # every dispatch after the first fetched one whole iteration
    assert snap["loop_ms"]["count"] == 9


def _check_always_ready(snap, logged):
    assert snap["starved_steps"] == 9          # every dispatch but the first
    assert sum(snap["starved_by"].values()) == 9
    assert [r["step"] for r in snap["starved"]] == list(range(2, 10))


def _check_gc(snap, logged):
    assert snap["gc_collections"] >= 1 and snap["gc_gen2"] >= 1
    assert snap["gc_pause_s"] > 0
    rec = _record(snap, 6)
    assert rec["gc_gen2"] >= 1 and rec["ms"]["gc"] > 0
    # the pause is the gc phase, not the callback's
    assert rec["ms"]["callback"] < rec["ms"]["gc"] + 1.0


def _check_slow_warning(snap, logged):
    (line,) = logged
    assert "step 8 took" in line and "feed_wait 3" in line


def _check_steady_no_warning(snap, logged):
    assert logged == []


_LOOP_CASES = {
    "callback_sleeps": (dict(on_log=_sleep_on(5, 0.05)), _check_callback),
    "feed_sleeps": (dict(feed_sleep=(5, 0.05)), _check_feed),
    "never_ready": (dict(ready=False, on_log=_sleep_on(5, 0.05)),
                    _check_never_ready),
    "always_ready": (dict(), _check_always_ready),
    "gc_in_on_log": (dict(on_log=lambda i, v: gc.collect() if i == 5
                          else None), _check_gc),
    "slow_iteration_warns": (dict(ready=False, feed_sleep=(8, 0.3)),
                             _check_slow_warning),
    "steady_run_is_silent": (dict(ready=False, n=40),
                             _check_steady_no_warning),
}


class TestLoopRecord:
    """``run_steps``' record of its own iterations (ISSUE 36): phases,
    the device run dry and why, the collector, the slow-iteration line.
    Readiness is the stub's, never the clock's."""

    @pytest.mark.parametrize("case", sorted(_LOOP_CASES))
    def test_the_loop_records_each_iteration(self, monkeypatch, caplog,
                                             case):
        kwargs, check = _LOOP_CASES[case]
        with caplog.at_level(logging.WARNING, logger="paddle_tpu.train"):
            snap = _stub_run(monkeypatch, **kwargs)
        assert snap["loop_ms"]["count"] == kwargs.get("n", 10) - 1
        check(snap, [r.getMessage() for r in caplog.records
                     if r.name == "paddle_tpu.train"])

    @pytest.mark.parametrize("raises", [False, True])
    def test_the_gc_callback_is_gone_after_the_call(self, raises):
        before = list(gc.callbacks)

        def step(params, opt_state, key, ids, labels, lr):
            if raises:
                raise RuntimeError("planted")
            return _Loss(True), params, opt_state
        if raises:
            with pytest.raises(RuntimeError, match="planted"):
                run_steps(step, {}, {}, _batches(3))
        else:
            run_steps(step, {}, {}, _batches(3))
        assert gc.callbacks == before


class TestPipelineStats:
    def test_split_keys_and_registry_lifecycle(self):
        data = _batches(4)
        pf = prefetch_to_device(iter(data), name="t_stats")
        list(pf)
        snap = profiler.pipeline_stats("t_stats")
        for k in ("host_blocked_s", "device_blocked_s",
                  "producer_blocked_s", "producer_busy_s", "bound",
                  "batches_in", "batches_out", "queue_depth_now"):
            assert k in snap, k
        assert snap["batches_out"] == 4
        assert snap["transfer_ms"]["count"] == 4
        assert snap["bound"] in ("input", "compute", "balanced")
        pf.close()
        assert "t_stats" not in profiler.pipeline_stats()
        with pytest.raises(KeyError):
            profiler.pipeline_stats("t_stats")

    @pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
    def test_place_by_spec_fallback_is_visible(self):
        """ISSUE 3 satellite: a spec that doesn't divide must warn AND
        show up in pipeline_stats()['placement_fallbacks'] with a
        one-line reason, instead of silently replicating."""
        from jax.sharding import Mesh, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("dp", "tp"))
        with pytest.warns(RuntimeWarning, match="does not divide"):
            arr = place_by_spec(np.zeros((3, 5), np.float32),
                                PartitionSpec("dp", "tp"), mesh,
                                name="w.qkv")
        # fell back to full replication, correctness preserved
        assert arr.sharding.spec == PartitionSpec()
        fallbacks = profiler.pipeline_stats()["placement_fallbacks"]
        assert any("w.qkv" in r and "replicating" in r for r in fallbacks)

    @pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
    def test_place_by_spec_dividing_spec_stays_silent(self):
        import warnings as _w

        from jax.sharding import Mesh, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("dp", "tp"))
        with _w.catch_warnings():
            _w.simplefilter("error")
            arr = place_by_spec(np.zeros((4, 8), np.float32),
                                PartitionSpec("dp", "tp"), mesh)
        assert arr.sharding.spec == PartitionSpec("dp", "tp")


class TestEndToEndOverlap:
    def test_prefetch_hides_slow_producer(self, gpt_step):
        """The acceptance shape at test scale: a producer with injected
        latency, sync loop vs prefetch+run_steps. The async side must be
        measurably faster AND still produce identical losses. (A timing
        assert as tight as a recovery share would flake under CI load,
        so here the bar is directional.)"""
        step, params, opt_state = gpt_step
        key = jax.random.key(1)
        n, delay = 8, 0.03
        data = [(RNG.randint(0, 256, (2, 8)).astype(np.int32),
                 RNG.randint(0, 256, (2, 8)).astype(np.int32))
                for _ in range(n)]

        def producer():
            for x, y in data:
                time.sleep(delay)
                yield x, y

        p, s = params, opt_state
        ref = []
        t0 = time.perf_counter()
        for i, (x, y) in enumerate(producer()):
            loss, p, s = step(p, s, jax.random.fold_in(key, i), x, y, 1e-3)
            ref.append(float(loss))
        t_sync = time.perf_counter() - t0

        with prefetch_to_device(producer(), depth=2,
                                name="t_overlap") as pf:
            t0 = time.perf_counter()
            _, _, losses = run_steps(step, params, opt_state, pf,
                                     key=key, lr=1e-3)
            t_async = time.perf_counter() - t0
            snap = pf.metrics.snapshot()
        np.testing.assert_allclose([float(l) for l in losses], ref,
                                   rtol=1e-6)
        assert t_async < t_sync
        # the split is populated: the run waited SOMEWHERE, and the
        # snapshot says where
        assert snap["host_blocked_s"] + snap["device_blocked_s"] > 0
