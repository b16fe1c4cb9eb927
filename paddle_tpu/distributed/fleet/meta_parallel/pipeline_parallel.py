"""Pipeline-parallel execution engine: 1F1B and interleaved schedules.

Capability parity with the reference (reference: fleet/meta_parallel/
pipeline_parallel.py — train_batch:657, forward_backward_pipeline (1F1B)
:440, interleaved :906; p2p meta handshake pp_utils/p2p_communication.py:52).

TPU-native design — a real pipeline, not a grad-accumulation loop:

* **Stage sub-meshes.** The device list is partitioned into one sub-mesh
  per pipeline stage; every chunk's params are ``jax.device_put`` onto its
  stage's sub-mesh at engine construction (the analog of each pp rank
  holding only its stage, reference pp_layers.py:237).
* **Per-stage jitted programs.** Each chunk gets a pure functional
  forward (and a vjp-recompute backward) compiled once per shape; the
  host drives the schedule, so no recompilation per microbatch
  (SURVEY §7.3 #1: per-stage jitted programs with host-driven schedule).
* **p2p activation transfer.** Stage boundaries move activations (fwd)
  and activation-grads (bwd) between sub-meshes with ``jax.device_put`` —
  the single-controller analog of the reference's isend/irecv pairs; no
  shape/dtype meta handshake is needed because XLA shapes are static.
* **1F1B order.** Every (virtual) stage executes the exact reference
  action sequence — warmup forwards (min(P-1-s, m)), steady 1F1B
  alternation, cooldown backwards — via a dependency-driven scheduler.
  Stage s therefore never holds more than min(P-s, m) in-flight
  microbatch stashes (the 1F1B memory bound; reference
  pipeline_parallel.py:440), which ``_peak_stash`` records for tests.
* **Backward = recompute.** The stashed state per in-flight microbatch is
  the stage *input* only; the backward jit recomputes the stage forward
  inside ``jax.vjp``. Memory ≤ the reference's 1F1B profile (which stashes
  all intermediate activations) at ~1/3 extra FLOPs, the standard
  trade on HBM-bound hardware.
* **Interleave.** ``PipelineParallelWithInterleave`` runs
  ``num_stages * v`` virtual chunks with chunk g placed on sub-mesh
  g % num_stages (reference :906's virtual-pipeline assignment); the same
  scheduler executes the longer virtual chain.

Because dispatch is async, stage k's XLA program runs concurrently with
stage k+1's on its own sub-mesh — the overlap the reference gets from its
actor-based FleetExecutor falls out of the dependency order.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ....core import random as _random
from ....core.autograd import tape_paused
from ....core.tensor import Tensor
from ....nn.layer.layers import _swapped_state
from .parallel_layers import PipelineLayer

__all__ = ["PipelineParallel", "PipelineParallelWithInterleave",
           "PipelineParallelWithInterleaveFthenB"]


def _unwrap(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


class _CountingProgram:
    """Thin wrapper over a jitted chunk program that counts executions on
    the owning engine (``_program_executes``) — schedule-efficiency
    benches use the count to price the per-dispatch floor separately from
    real schedule cost. Passes ``_cache_size`` through for the retrace
    accounting."""

    def __init__(self, fn, owner):
        self._fn = fn
        self._owner = owner

    def __call__(self, *args, **kwargs):
        self._owner._program_executes += 1
        return self._fn(*args, **kwargs)

    def _cache_size(self):
        return self._fn._cache_size()


class PipelineParallel:
    def __init__(self, layers, hcg=None, strategy=None, devices=None,
                 stage_mesh_axes=None, batch_axis=None):
        """``stage_mesh_axes``: optional named shape for each stage's
        sub-mesh, e.g. ``{"dp": 2, "tp": 2}`` — the hybrid pp x tp x dp
        topology of the reference's HybridCommunicateGroup (§3.3 north
        star). Stage params pre-sharded over those axes keep their layout;
        ``batch_axis`` names the axis microbatch activations shard over
        (data parallelism within each stage)."""
        if not isinstance(layers, PipelineLayer):
            raise TypeError("PipelineParallel requires a PipelineLayer")
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        pcfg = (strategy.pipeline_configs if strategy is not None
                else {"accumulate_steps": 1, "micro_batch_size": 1})
        self.accumulate_steps = pcfg.get("accumulate_steps", 1)
        self.micro_batch_size = pcfg.get("micro_batch_size", 1)
        self.num_stages = layers.get_num_stages()
        self.num_chunks = layers.get_num_chunks()
        self.training = True
        self._batch_count = 0
        self._programs: Dict = {}  # (chunk, kind, train) -> jitted fn
        # device-program executions since construction: the schedule's
        # dispatch count, used by benches to separate the per-dispatch
        # floor from real schedule cost
        self._program_executes = 0
        self._peak_stash: List[int] = [0] * self.num_chunks
        self._stage_mesh_axes = dict(stage_mesh_axes or {})
        self._batch_axis = batch_axis
        if batch_axis is not None and batch_axis not in self._stage_mesh_axes:
            raise ValueError(
                f"batch_axis '{batch_axis}' not in stage_mesh_axes "
                f"{list(self._stage_mesh_axes)}")
        self._build_meshes(devices)
        self._collect_chunk_params()
        self._place_params()

    # -- sub-mesh construction ----------------------------------------------
    def _build_meshes(self, devices):
        from jax.sharding import Mesh, NamedSharding

        devs = list(devices) if devices is not None else list(jax.devices())
        p = self.num_stages
        per = len(devs) // p
        axes = self._stage_mesh_axes
        if axes:
            size = int(np.prod(list(axes.values())))
            if per != size:
                raise ValueError(
                    f"stage_mesh_axes {axes} needs {size} devices/stage, "
                    f"have {per} ({len(devs)} over {p} stages)")
        self._stage_meshes = []
        for s in range(p):
            sub = (devs[s * per:(s + 1) * per] if per >= 1
                   else [devs[s % len(devs)]])
            if axes:
                self._stage_meshes.append(Mesh(
                    np.array(sub).reshape(tuple(axes.values())),
                    tuple(axes)))
            else:
                self._stage_meshes.append(
                    Mesh(np.array(sub), ("stage_data",)))
        from paddle_tpu.distributed.spec_layout import default_layout
        self._stage_shardings = [
            NamedSharding(m, default_layout().replicated())
            for m in self._stage_meshes]
        # expose placements so the stateful PipelineLayer.forward can hop
        self._layers._stage_shardings = [
            self._chunk_sharding(c) for c in range(self.num_chunks)]
        self._layers._engine_fetch = self._fetch_chunk_params

    def _chunk_mesh_idx(self, chunk: int) -> int:
        return chunk % self.num_stages

    def _chunk_sharding(self, chunk: int):
        return self._stage_shardings[self._chunk_mesh_idx(chunk)]

    # -- param bookkeeping ---------------------------------------------------
    def _collect_chunk_params(self):
        """Canonical (dedup'd) param names used by each chunk; shared layers
        (tied embeddings) appear in every chunk that runs them and their
        grads are summed at write-back — the single-controller equivalent of
        allreduce_shared_weight_gradients over the pp group."""
        pipe_params = dict(self._layers.named_parameters())
        self._param_objs = pipe_params
        self._chunk_param_names: List[List[str]] = []
        for c in range(self.num_chunks):
            ids = set()
            for lyr in self._layers.stage_layers(c):
                for p in lyr.parameters():
                    ids.add(id(p))
            self._chunk_param_names.append(
                [n for n, p in pipe_params.items() if id(p) in ids])

    def _place_params(self):
        """Params (and buffers) of chunk c live on stage sub-mesh c % p.
        Shared params stay on the first chunk that owns them. Params that
        are already partitioned (TP/FSDP layouts) are never silently
        re-replicated: they must already sit inside their stage's sub-mesh."""
        placed = set()
        for c in range(self.num_chunks):
            sh = self._chunk_sharding(c)
            stage_ids = {d.id for d in sh.mesh.devices.flat}
            for n in self._chunk_param_names[c]:
                p = self._param_objs[n]
                if id(p) in placed:
                    continue
                placed.add(id(p))
                psh = getattr(p._data, "sharding", None)
                if psh is not None and not psh.is_fully_replicated:
                    have = {d.id for d in psh.device_set}
                    if not have <= stage_ids:
                        raise NotImplementedError(
                            f"param '{n}' is partitioned over devices "
                            f"{sorted(have)} but its pipeline stage owns "
                            f"{sorted(stage_ids)}; shard TP/FSDP params "
                            "inside the stage sub-mesh before wrapping in "
                            "PipelineParallel")
                    continue  # keep the existing partitioned layout
                p._data = jax.device_put(p._data, sh)
            for lyr in self._layers.stage_layers(c):
                for _, b in lyr.named_buffers():
                    if b is not None and id(b) not in placed:
                        placed.add(id(b))
                        b._data = jax.device_put(b._data, sh)

    def _fetch_chunk_params(self, c: int) -> Dict[str, jnp.ndarray]:
        """Current param arrays for chunk c, transferred to its sub-mesh if
        the canonical copy lives elsewhere (shared/tied weights). Params
        already on the stage's device set (incl. TP/FSDP layouts) pass
        through untouched."""
        sh = self._chunk_sharding(c)
        stage_ids = {d.id for d in sh.mesh.devices.flat}
        out = {}
        for n in self._chunk_param_names[c]:
            arr = self._param_objs[n]._data
            psh = getattr(arr, "sharding", None)
            if psh is None or {d.id for d in psh.device_set} != stage_ids:
                arr = jax.device_put(arr, sh)
            out[n] = arr
        return out

    # -- per-chunk programs ---------------------------------------------------
    def _chunk_f(self, c: int):
        pipe = self._layers

        def f(params, x, key):
            with _random.key_context(key):
                with _swapped_state(pipe, params), tape_paused():
                    out = pipe.forward_stage(Tensor(x), c)
            return out._data
        return f

    def _loss_f(self, c: int):
        pipe = self._layers
        f = self._chunk_f(c)

        def floss(params, x, label, key):
            out = f(params, x, key)
            with _swapped_state(pipe, params), tape_paused():
                loss = pipe._loss_fn(Tensor(out), Tensor(label))
            return loss._data
        return floss

    def _program(self, c: int, kind: str):
        key = (c, kind, self._layers.training)
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        f = self._chunk_f(c)
        last = c == self.num_chunks - 1
        if kind == "fwd":
            prog = jax.jit(f)
        elif kind == "loss_fwd":
            prog = jax.jit(self._loss_f(c))
        elif kind == "bwd":
            assert not last

            def bwd(params, x, key, g):
                _, vjp = jax.vjp(lambda p, xx: f(p, xx, key), params, x)
                return vjp(g)  # (dparams, dx)
            prog = jax.jit(bwd)
        elif kind == "loss_bwd":
            floss = self._loss_f(c)

            def loss_bwd(params, x, label, key, gscale):
                loss, vjp = jax.vjp(
                    lambda p, xx: floss(p, xx, label, key), params, x)
                # cotangent = gscale: grads of the scaled loss, one forward
                dparams, dx = vjp(gscale.astype(loss.dtype))
                return loss, dparams, dx
            prog = jax.jit(loss_bwd)
        else:
            raise ValueError(kind)
        prog = _CountingProgram(prog, self)
        self._programs[key] = prog
        return prog

    # -- API parity --------------------------------------------------------
    def train(self):
        self.training = True
        self._layers.train()
        return self

    def eval(self):
        self.training = False
        self._layers.eval()
        return self

    def parameters(self, *a, **k):
        return self._layers.parameters(*a, **k)

    def named_parameters(self, *a, **k):
        return self._layers.named_parameters(*a, **k)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        return self._layers.set_state_dict(*a, **k)

    def __call__(self, x):
        return self._layers(x)

    def forward(self, x):
        return self._layers(x)

    # -- schedule ----------------------------------------------------------
    def _split_micro(self, data):
        x, y = data
        n = self.accumulate_steps
        xa, ya = _unwrap(x), _unwrap(y)
        bs = xa.shape[0]
        assert bs % n == 0, f"batch {bs} not divisible by accumulate_steps {n}"
        mb = bs // n
        return [(xa[i * mb:(i + 1) * mb], ya[i * mb:(i + 1) * mb])
                for i in range(n)]

    def _next_batch_key(self):
        """Per-batch dropout key derived from the CURRENT global seed (so
        paddle.seed() after engine construction takes effect, like the
        non-pipeline path) and a per-batch counter (eval advances it too)."""
        seed = getattr(_random.default_generator, "_seed", 0)
        k = jax.random.fold_in(jax.random.key(seed), self._batch_count)
        self._batch_count += 1
        return k

    @staticmethod
    def _schedule_queue(vs: int, n_vstages: int, m: int) -> deque:
        """The per-(virtual-)stage action order; subclasses override to
        change the schedule. Default is 1F1B (reference
        pipeline_parallel.py:440): warmup forwards, steady F/B alternation,
        cooldown backwards — stage s never stashes more than min(P-s, m)
        microbatch inputs."""
        warmup = min(n_vstages - 1 - vs, m)
        q = [("F", i) for i in range(warmup)]
        for k in range(m - warmup):
            q.append(("F", warmup + k))
            q.append(("B", k))
        q.extend(("B", k) for k in range(m - warmup, m))
        return deque(q)

    def _transfer(self, arr, chunk: int):
        """Activation / activation-grad hop onto ``chunk``'s sub-mesh — the
        p2p edge of the pipeline (reference p2p_communication.py:313).
        With ``batch_axis`` the microbatch rows shard over that stage axis
        (dp within the stage); otherwise activations replicate."""
        from jax.sharding import NamedSharding

        from paddle_tpu.distributed.spec_layout import SpecLayout
        mesh = self._stage_meshes[self._chunk_mesh_idx(chunk)]
        ba = self._batch_axis
        if (ba is not None and getattr(arr, "ndim", 0) >= 1
                and arr.shape[0] % self._stage_mesh_axes[ba] == 0):
            sh = NamedSharding(
                mesh, SpecLayout(data_axis=ba).batch(arr.ndim))
        else:
            sh = self._chunk_sharding(chunk)
        if getattr(arr, "sharding", None) == sh:
            return arr
        return jax.device_put(arr, sh)

    def forward_backward_pipeline(self, data, scaler=None):
        if self._layers._loss_fn is None:
            raise ValueError(
                "training through the pipeline engine requires the "
                "PipelineLayer to be built with loss_fn (the last stage "
                "computes the loss; reference pp_layers.py:237)")
        micro = self._split_micro(data)
        m = len(micro)
        nv = self.num_chunks
        batch_key = self._next_batch_key()
        gscale = 1.0 / m
        # only pre-scale grads when the scaler will actually unscale them in
        # step(); bf16/amp-off is a passthrough (GradScaler._passthrough)
        if scaler is not None and not scaler._passthrough():
            gscale = gscale * float(scaler._scale)

        chunk_params = [self._fetch_chunk_params(c) for c in range(nv)]
        acts = {(0, i): self._transfer(mx, 0) for i, (mx, _) in enumerate(micro)}
        labels = [self._transfer(my, nv - 1) for _, my in micro]
        gout: Dict = {}
        stash: List[Dict] = [dict() for _ in range(nv)]
        grad_acc: List[Dict[str, jnp.ndarray]] = [dict() for _ in range(nv)]
        queues = [self._schedule_queue(vs, nv, m) for vs in range(nv)]
        self._peak_stash = [0] * nv
        losses = []

        def mbkey(vs, i):
            return jax.random.fold_in(batch_key, vs * m + i)

        remaining = sum(len(q) for q in queues)
        while remaining:
            progressed = False
            for vs in range(nv):
                if not queues[vs]:
                    continue
                kind, i = queues[vs][0]
                last = vs == nv - 1
                if kind == "F":
                    if (vs, i) not in acts:
                        continue
                    x = acts.pop((vs, i))
                    if not last:
                        y = self._program(vs, "fwd")(
                            chunk_params[vs], x, mbkey(vs, i))
                        acts[(vs + 1, i)] = self._transfer(y, vs + 1)
                    # the last chunk only stashes here: its B (which 1F1B
                    # runs immediately after) computes loss AND grads in one
                    # forward via the loss_bwd program
                    stash[vs][i] = x
                    self._peak_stash[vs] = max(self._peak_stash[vs],
                                               len(stash[vs]))
                else:  # B
                    if last:
                        x = stash[vs].pop(i)
                        loss, dparams, dx = self._program(vs, "loss_bwd")(
                            chunk_params[vs], x, labels[i], mbkey(vs, i),
                            jnp.float32(gscale))
                        losses.append(loss)
                    else:
                        if (vs, i) not in gout:
                            continue
                        g = gout.pop((vs, i))
                        x = stash[vs].pop(i)
                        dparams, dx = self._program(vs, "bwd")(
                            chunk_params[vs], x, mbkey(vs, i), g)
                    for n, d in dparams.items():
                        acc = grad_acc[vs].get(n)
                        grad_acc[vs][n] = d if acc is None else acc + d
                    if vs > 0:
                        gout[(vs - 1, i)] = self._transfer(dx, vs - 1)
                queues[vs].popleft()
                remaining -= 1
                progressed = True
            if not progressed:
                raise RuntimeError(
                    "pipeline schedule deadlock: no stage can make progress "
                    f"(queues={[list(q)[:2] for q in queues]})")

        self._write_back_grads(grad_acc)
        total = losses[0]
        for l in losses[1:]:
            total = total + l
        return Tensor(total / m)

    def _write_back_grads(self, grad_acc):
        """Accumulate functional grads into the stateful ``.grad`` slots the
        optimizer consumes; shared-weight contributions from different
        chunks are moved to the canonical copy's sub-mesh and summed."""
        for vs, accs in enumerate(grad_acc):
            for n, g in accs.items():
                p = self._param_objs[n]
                sh = getattr(p._data, "sharding", None)
                if sh is not None and getattr(g, "sharding", None) != sh:
                    g = jax.device_put(g, sh)
                if p.grad is None:
                    p.grad = Tensor(g)
                else:
                    p.grad = Tensor(p.grad._data + g)

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        """Parity: PipelineParallel.train_batch (pipeline_parallel.py:657)."""
        assert self.training, "call train() before train_batch"
        loss = self.forward_backward_pipeline(data, scaler)
        if scaler is not None:
            scaler.step(optimizer)
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return loss

    def eval_batch(self, data, compute_loss=True):
        micro = self._split_micro(data)
        nv = self.num_chunks
        chunk_params = [self._fetch_chunk_params(c) for c in range(nv)]
        batch_key = self._next_batch_key()
        total = None
        for i, (mx, my) in enumerate(micro):
            x = self._transfer(mx, 0)
            for vs in range(nv - 1):
                x = self._transfer(
                    self._program(vs, "fwd")(
                        chunk_params[vs], x,
                        jax.random.fold_in(batch_key, vs * len(micro) + i)),
                    vs + 1)
            lastk = jax.random.fold_in(batch_key, (nv - 1) * len(micro) + i)
            if compute_loss and self._layers._loss_fn is not None:
                out = self._program(nv - 1, "loss_fwd")(
                    chunk_params[nv - 1], x, self._transfer(my, nv - 1),
                    lastk)
            else:
                out = self._program(nv - 1, "fwd")(
                    chunk_params[nv - 1], x, lastk)
            total = out if total is None else total + out
        return Tensor(total / len(micro))


class PipelineParallelWithInterleave(PipelineParallel):
    """Interleaved virtual-pipeline schedule (reference
    pipeline_parallel.py:906): the layer list is cut into
    ``num_stages * num_virtual_stages`` chunks and chunk g is placed on
    stage sub-mesh g % num_stages, so each physical stage alternates
    between its model chunks — the bubble-shrinking property of the
    interleaved schedule under async dispatch. Construct the
    ``PipelineLayer`` with ``num_virtual_pipeline_stages`` to match."""

    def __init__(self, layers, hcg=None, strategy=None,
                 num_virtual_stages=None, devices=None):
        if num_virtual_stages is not None and \
                layers.get_num_chunks() != \
                layers.get_num_stages() * num_virtual_stages:
            raise ValueError(
                f"PipelineLayer was built with "
                f"{layers.get_num_chunks() // layers.get_num_stages()} "
                f"virtual stages, engine asked for {num_virtual_stages}")
        super().__init__(layers, hcg, strategy, devices=devices)
        self.num_virtual_stages = (
            num_virtual_stages
            or layers.get_num_chunks() // layers.get_num_stages())


class PipelineParallelWithInterleaveFthenB(PipelineParallelWithInterleave):
    """F-then-B interleaved schedule (reference pipeline_parallel.py:1489):
    every microbatch's forward completes before any backward starts, with
    backwards draining in reverse virtual-chunk order (the reference's
    ``_get_virtual_pp_rank(..., forward=False)`` reversal falls out of the
    dependency order here). Peak activation memory is the full ``m``
    stashes per stage — the trade the reference makes for a schedule
    whose collective-overlap windows are contiguous."""

    @staticmethod
    def _schedule_queue(vs: int, n_vstages: int, m: int) -> deque:
        return deque([("F", i) for i in range(m)]
                     + [("B", i) for i in range(m)])
