"""Solver family: Caffe-exact update rules as pure, jitted transforms.

Replaces the reference's ``Solver``/``SGDSolver`` hierarchy
(``caffe/src/caffe/solver.cpp``, ``solvers/*.cpp``) and the worker facade
``CaffeNet.train/test`` (``src/main/scala/libs/Net.scala:102-119``):

- ``Solver::Step(iters)``  ->  ``Solver.step(tau)`` — a ``lax.scan`` over tau
  iterations inside one jitted function: ClearParamDiffs is free (grads are
  fresh values), iter_size microbatch accumulation, LR policy, update rule,
  in one fused XLA program per round instead of per-layer kernel launches.
- update history blobs (``SGDSolver::history_``)  ->  ``TrainState.history``
  pytree, donated between steps so updates are in-place in HBM.
- ``TestAndStoreResult`` (SparkNet-added, ``solver.cpp:413-444``)  ->
  ``Solver.test_and_store_result`` returning raw accumulated per-output
  scores for driver-side aggregation.

Semantics matched to the reference (``sgd_solver.cpp``):
- momentum formula ``v = m*v + local_lr*(grad + decay*w); w -= v`` (decay
  inside the gradient, *before* momentum — not the optax convention),
- 7 LR policies with the exact formulas at ``sgd_solver.cpp:27-64``,
- clip_gradients on the raw accumulated grads before normalization,
- per-param lr_mult/decay_mult, L1/L2 regularization_type,
- Nesterov/AdaGrad/RMSProp/AdaDelta/Adam per ``solvers/*.cpp``.
"""

from __future__ import annotations

import collections
import inspect
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu import obs
from sparknet_tpu.obs import health as _health
from sparknet_tpu.obs import profile as _profile
from sparknet_tpu.config import load_net_prototxt
from sparknet_tpu.config.schema import NetParameter, SolverParameter, solver_method
from sparknet_tpu.net import JaxNet, Params, Stats
from sparknet_tpu.utils.rngs import default_train_key


class TrainState(NamedTuple):
    """Everything the reference snapshots: params + SolverState (iter,
    history) + BN stats (which the reference keeps inside params)."""

    params: Params
    stats: Stats
    history: Any  # per-method pytree(s) shaped like params
    iter: jnp.ndarray  # scalar int32


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _zeros_like(params):
    return _tree_map(jnp.zeros_like, params)


# ---------------------------------------------------------------------------
# LR policies (reference: sgd_solver.cpp:27-64)
# ---------------------------------------------------------------------------


def learning_rate(p: SolverParameter, it):
    """Rate at iteration ``it`` (traced-friendly: jnp ops only)."""
    it = jnp.asarray(it, jnp.float32)
    policy = p.lr_policy
    base = p.base_lr
    if policy == "fixed":
        return jnp.asarray(base, jnp.float32)
    if policy == "step":
        return base * jnp.power(p.gamma, jnp.floor(it / p.stepsize))
    if policy == "exp":
        return base * jnp.power(p.gamma, it)
    if policy == "inv":
        return base * jnp.power(1.0 + p.gamma * it, -p.power)
    if policy == "multistep":
        sv = jnp.asarray(p.stepvalue or [jnp.inf], jnp.float32)
        current_step = jnp.sum(it >= sv).astype(jnp.float32)
        return base * jnp.power(p.gamma, current_step)
    if policy == "poly":
        return base * jnp.power(1.0 - it / max(1, p.max_iter), p.power)
    if policy == "sigmoid":
        return base / (1.0 + jnp.exp(-p.gamma * (it - p.stepsize)))
    raise ValueError(f"unknown lr_policy {policy!r}")


# ---------------------------------------------------------------------------
# Update rules (reference: solvers/*.cpp ComputeUpdateValue)
# ---------------------------------------------------------------------------


def _init_history(method: str, params):
    if method in ("SGD", "NESTEROV", "ADAGRAD", "RMSPROP"):
        return _zeros_like(params)
    if method in ("ADADELTA", "ADAM"):
        return (_zeros_like(params), _zeros_like(params))
    raise ValueError(f"unknown solver method {method!r}")


def _compute_update(method, p: SolverParameter, g, w, hist, local_rate, it):
    """Per-blob update value + new history. Mirrors each reference solver's
    ComputeUpdateValue exactly."""
    if method == "SGD":
        v = p.momentum * hist + local_rate * g
        return v, v
    if method == "NESTEROV":
        v = p.momentum * hist + local_rate * g
        update = (1.0 + p.momentum) * v - p.momentum * hist
        return update, v
    if method == "ADAGRAD":
        acc = hist + g * g
        return local_rate * g / (jnp.sqrt(acc) + p.delta), acc
    if method == "RMSPROP":
        acc = p.rms_decay * hist + (1.0 - p.rms_decay) * g * g
        return local_rate * g / (jnp.sqrt(acc) + p.delta), acc
    if method == "ADADELTA":
        acc_g, acc_x = hist
        m = p.momentum
        acc_g = m * acc_g + (1.0 - m) * g * g
        upd = g * jnp.sqrt((acc_x + p.delta) / (acc_g + p.delta))
        acc_x = m * acc_x + (1.0 - m) * upd * upd
        return local_rate * upd, (acc_g, acc_x)
    if method == "ADAM":
        m_t, v_t = hist
        b1, b2 = p.momentum, p.momentum2
        t = jnp.asarray(it, jnp.float32) + 1.0
        m_t = b1 * m_t + (1.0 - b1) * g
        v_t = b2 * v_t + (1.0 - b2) * g * g
        corr = jnp.sqrt(1.0 - jnp.power(b2, t)) / (1.0 - jnp.power(b1, t))
        return local_rate * corr * m_t / (jnp.sqrt(v_t) + p.delta), (m_t, v_t)
    raise ValueError(f"unknown solver method {method!r}")


def _hist_for(method, history, key, idx):
    if method in ("ADADELTA", "ADAM"):
        return (history[0][key][idx], history[1][key][idx])
    return history[key][idx]


def _set_hist(method, new_history, key, idx, value):
    if method in ("ADADELTA", "ADAM"):
        new_history[0].setdefault(key, {})[idx] = value[0]
        new_history[1].setdefault(key, {})[idx] = value[1]
    else:
        new_history.setdefault(key, {})[idx] = value


class Solver:
    """Driver-facing solver (the ``CaffeNet`` + ``Solver`` roles in one).

    Typical use::

        solver = Solver(solver_param, feed_shapes={...})
        state = solver.init_state(seed=0)
        state, losses = solver.step(state, stacked_batches)   # tau iters
        scores = solver.test_and_store_result(state, test_batches)
    """

    def __init__(
        self,
        param: SolverParameter,
        net_param: Optional[NetParameter] = None,
        feed_shapes: Optional[Dict[str, Sequence[int]]] = None,
        test_feed_shapes: Optional[Dict[str, Sequence[int]]] = None,
        compute_dtype: Optional[str] = None,
        train_transform=None,
        test_transform=None,
        audit: bool = False,
        net=None,
    ):
        # Per-phase preprocessing closures traced into the jitted step —
        # the reference's imageNetTrain/TestPreprocessing host closures
        # (ImageNetApp.scala:128-180) moved on-device.  train_transform:
        # (batch, rng) -> batch; test_transform: (batch) -> batch.
        self.train_transform = train_transform
        self.test_transform = test_transform
        # in-graph numerics audit (obs/health.py): when True, the step
        # additionally returns a small per-iteration stats tree (grad
        # norm, per-group param/update norms, non-finite counts) FUSED
        # into the same jitted program — ``step`` then returns
        # ``(state, losses, stats)``.  Pure readouts: the trajectory is
        # bit-identical audit on/off (tests/test_health.py).  May be
        # flipped after construction but BEFORE the first step (the jit
        # traces lazily).
        self.audit = bool(audit)
        self.param = param
        self.compute_dtype = compute_dtype
        self.method = solver_method(param)
        if net is not None:
            # any loss-bearing apply-fn object (init / loss_fn /
            # param_multipliers / feed_blobs — models/transformer_lm.py
            # is the reference implementation): the prototxt graph
            # machinery is bypassed entirely, everything downstream
            # (update rules, audit, trainers, checkpoints) is pytree-
            # generic and composes unchanged.
            if net_param is not None:
                raise ValueError("pass net= or net_param=, not both")
            if compute_dtype is not None:
                # the precision is stated once, here, as for JaxNet: a net
                # object that computes in a lower dtype over float32 master
                # weights takes it through ``set_compute_dtype``
                # (models/hybrid_lm.py); one with float32 written into its
                # forward (TransformerLM) cannot honour it
                if not hasattr(net, "set_compute_dtype"):
                    raise ValueError(
                        f"compute_dtype={compute_dtype!r}: "
                        f"{type(net).__name__} has no set_compute_dtype"
                    )
                net.set_compute_dtype(compute_dtype)
            self.net_param = getattr(net, "net_param", None)
            self.net = net
        else:
            if net_param is not None:
                netp = net_param
            else:
                from sparknet_tpu.config import resolve_solver_net

                netp = resolve_solver_net(param)
            self.net_param = netp
            self.net = JaxNet(
                netp,
                phase="TRAIN",
                feed_shapes=feed_shapes,
                compute_dtype=compute_dtype,
            )
        self._test_feed_shapes = test_feed_shapes or feed_shapes
        self._test_net: Optional[JaxNet] = None
        self._lr_mults, self._decay_mults = self.net.param_multipliers()
        self._loss_window = collections.deque(maxlen=max(1, param.average_loss))
        # per-tau-window loss arrays not yet pulled to host: smoothed_loss
        # materializes them on read.  Keeping the hot loop free of
        # device->host syncs is standard TPU async-dispatch discipline.
        self._pending_losses: list = []
        # obs.Program: built and accounted for on batches not seen before
        # (obs/program.py), then the jax.jit is called as ever
        self._jit_step = obs.Program(
            "step", jax.jit(self._step_tau, donate_argnums=(0,)), watch=(1,)
        )
        self._jit_forward_test = obs.Program(
            "forward_test", jax.jit(self._forward_test), watch=(2,)
        )

    @property
    def test_net(self) -> JaxNet:
        """TEST-phase view sharing the train weights, built lazily — the
        reference only constructs test nets when test config exists
        (Solver::InitTestNets, solver.cpp:104-190), and a train-only config
        has no valid TEST filtering."""
        if self._test_net is None:
            if self.net_param is None:
                raise ValueError(
                    "this solver wraps a net object (net=...) with no "
                    "prototxt TEST view — score through the net's own "
                    "forward/loss_fn instead"
                )
            self._test_net = JaxNet(
                self.net_param,
                phase="TEST",
                feed_shapes=self._test_feed_shapes,
                compute_dtype=self.compute_dtype,
            )
        return self._test_net

    # ------------------------------------------------------------------
    def init_state(self, seed: int = 0) -> TrainState:
        if self.param.random_seed >= 0:
            seed = self.param.random_seed
        params, stats = self.net.init(seed)
        return TrainState(
            params=params,
            stats=stats,
            history=_init_history(self.method, params),
            iter=jnp.asarray(0, jnp.int32),
        )

    # ------------------------------------------------------------------
    # One iteration: iter_size microbatches -> grads -> update
    # ------------------------------------------------------------------
    def _transform_train(self, batch, rng):
        """The train transform on one minibatch, under the scope
        ``transform``.  A closure that declares a ``dtype`` parameter
        (``data/transforms.train_transform``) is handed the net's compute
        dtype, so the batch is rounded once, there; any other
        ``(batch, rng) -> batch`` callable is called as it is."""
        fn = self.train_transform
        if fn is None:
            return batch
        rng = jax.random.fold_in(rng, 0x7F)
        with jax.named_scope("transform"):
            if "dtype" in inspect.signature(fn).parameters:
                return fn(batch, rng, self.compute_dtype or jnp.float32)
            return fn(batch, rng)

    def _grads(self, params, stats, batch, rng):
        grad_fn = jax.value_and_grad(self.net.loss_fn, has_aux=True)
        if self.param.iter_size == 1:
            batch = self._transform_train(batch, rng)
            (loss, (_, new_stats)), g = grad_fn(params, stats, batch, rng, True)
            return g, loss, new_stats

        def micro(carry, mb):
            acc, st, i = carry
            lrng = jax.random.fold_in(rng, i)
            mb = self._transform_train(mb, lrng)
            (loss, (_, st2)), g = grad_fn(params, st, mb, lrng, True)
            return (_tree_map(jnp.add, acc, g), st2, i + 1), loss

        zero = _zeros_like(params)
        (g, new_stats, _), losses = jax.lax.scan(micro, (zero, stats, 0), batch)
        return g, jnp.mean(losses), new_stats

    def _apply_update(self, params, history, grads, it):
        p = self.param
        # the raw-grad global L2: ClipGradients' reduction
        # (sgd_solver.cpp:84-100), computed ONCE and shared with the
        # numerics audit (obs/health.py) when that is on
        grad_norm = None
        if p.clip_gradients > 0 or self.audit:
            leaves = jax.tree_util.tree_leaves(grads)
            sumsq = sum(jnp.sum(jnp.square(g)) for g in leaves)
            grad_norm = jnp.sqrt(sumsq)
        # ClipGradients on raw accumulated grads (sgd_solver.cpp:84-100)
        if p.clip_gradients > 0:
            norm = grad_norm
            scale = jnp.where(
                norm > p.clip_gradients, p.clip_gradients / norm, 1.0
            )
            grads = _tree_map(lambda g: g * scale, grads)
        rate = learning_rate(p, it)
        inv_iter_size = 1.0 / max(1, p.iter_size)
        new_params: Params = {}
        if self.method in ("ADADELTA", "ADAM"):
            new_history: Any = ({}, {})
        else:
            new_history = {}
        for key, blobs in params.items():
            new_params[key] = []
            for idx, w in enumerate(blobs):
                # update math always in the master dtype (f32), even when
                # the net computes in bf16
                g = grads[key][idx].astype(w.dtype) * inv_iter_size  # Normalize
                lr_mult = self._lr_mults[key][idx]
                decay_mult = self._decay_mults[key][idx]
                decay = p.weight_decay * decay_mult
                if decay:
                    if p.regularization_type == "L1":
                        g = g + decay * jnp.sign(w)  # Regularize L1
                    else:
                        g = g + decay * w  # Regularize L2
                hist = _hist_for(self.method, history, key, idx)
                update, new_h = _compute_update(
                    self.method, p, g, w, hist, rate * lr_mult, it
                )
                _set_hist(self.method, new_history, key, idx, new_h)
                new_params[key].append(w - update)  # Net::Update
        if self.method in ("ADADELTA", "ADAM"):
            new_history = (
                {k: [new_history[0][k][i] for i in range(len(params[k]))] for k in params},
                {k: [new_history[1][k][i] for i in range(len(params[k]))] for k in params},
            )
        else:
            new_history = {
                k: [new_history[k][i] for i in range(len(params[k]))] for k in params
            }
        return new_params, new_history, grad_norm

    def _one_iter(self, st: TrainState, batch, rng):
        """One solver iteration (shared by both scan bodies).  With the
        audit on, the per-iter output is ``(loss, stats)`` — the stats
        tree is computed from values the update already produced (pure
        readout, fused into the same program)."""
        lrng = jax.random.fold_in(rng, st.iter)
        grads, loss, new_stats = self._grads(st.params, st.stats, batch, lrng)
        with jax.named_scope("update"):
            new_params, new_history, grad_norm = self._apply_update(
                st.params, st.history, grads, st.iter
            )
        new_st = TrainState(new_params, new_stats, new_history, st.iter + 1)
        if self.audit:
            stats = _health.audit_iteration(
                grads, st.params, new_params, loss, grad_norm
            )
            return new_st, (loss, stats)
        return new_st, loss

    def _step_tau(self, state: TrainState, batches, rng):
        """tau iterations under lax.scan (batches stacked on axis 0).
        Returns ``(state, losses)`` — or ``(state, (losses, stats))``
        with the numerics audit on (leaves gain a leading tau axis)."""

        def one_iter(st: TrainState, batch):
            return self._one_iter(st, batch, rng)

        return jax.lax.scan(one_iter, state, batches)

    def _step_repeat(self, state: TrainState, batch, rng, tau: int):
        """tau iterations reusing one batch (no per-iter host dispatch) —
        the benchmarking fast path."""

        def one_iter(st: TrainState, _):
            return self._one_iter(st, batch, rng)

        return jax.lax.scan(one_iter, state, None, length=tau)

    def step_repeat(self, state: TrainState, batch, tau: int, rng=None):
        """Run ``tau`` iterations on the SAME device-resident batch inside
        one jitted program.  One dispatch for the whole window — use for
        probes (``tools/perf_probe.py``) or single-batch overfit tests."""
        rng = rng if rng is not None else default_train_key(0)
        if not hasattr(self, "_jit_step_repeat"):
            self._jit_step_repeat = obs.Program("step_repeat", jax.jit(
                self._step_repeat, donate_argnums=(0,), static_argnums=(3,)
            ), watch=(1, 3))
        state, out = self._jit_step_repeat(state, batch, rng, tau)
        if self.audit:
            losses, stats = out
            self.note_losses(losses)
            return state, losses, stats
        losses = out
        self.note_losses(losses)
        return state, losses

    def step(
        self, state: TrainState, batches: Dict[str, jax.Array], rng=None
    ) -> Tuple[TrainState, jax.Array]:
        """Run ``tau`` iterations where tau is the leading axis of every
        entry in ``batches`` (the ``solver_step(state, tau)`` analog,
        ccaffe.cpp:230-233).  Returns (new_state, per-iter losses) — or
        (new_state, losses, audit_stats) when the numerics audit is on
        (``audit=True``; see obs/health.py)."""
        rng = rng if rng is not None else default_train_key(0)
        if self.param.debug_info:
            first = jax.tree_util.tree_map(lambda x: x[0], batches)
            self.debug_info_pass(state, first, rng=rng)
        # the single-process round phase ("execute" in the obs span
        # vocabulary — cli train's default path has no trainer wrapper)
        with obs.span("execute"):
            state, out = self._jit_step(state, batches, rng)
        stats = None
        if self.audit:
            losses, stats = out
        else:
            losses = out
        self.note_losses(losses)
        tm = obs.training_metrics()
        if tm is not None:
            tm.rounds.inc()
            tm.iters.inc(losses.shape[0])  # tau (shape read: no sync)
        _profile.observe_round_if_active(losses)  # --profile round mark
        obs.report_healthy()
        if self.audit:
            return state, losses, stats
        return state, losses

    def note_losses(self, losses) -> None:
        """Record a tau-window's per-iter losses for ``smoothed_loss``
        WITHOUT a device->host transfer (that sync happens lazily when
        smoothed_loss is read — solver.cpp:225-234 computes the window
        eagerly, but it runs on-host; here the fetch would serialize the
        async dispatch queue)."""
        self._pending_losses.append(losses)
        # the window needs at most its last ``maxlen`` values and every
        # pending array carries >=1, so older arrays can never reach it
        # — drop them (bounds device-buffer retention when the caller
        # never reads smoothed_loss)
        excess = len(self._pending_losses) - self._loss_window.maxlen
        if excess > 0:
            del self._pending_losses[:excess]

    def _drain_losses(self) -> None:
        if not self._pending_losses:
            return
        pending, self._pending_losses = self._pending_losses, []
        for arr in pending:
            if getattr(arr, "ndim", 0) == 2:
                # trainer rounds: (workers, tau) — window sees the
                # worker-mean of the ADDRESSABLE shards only (a
                # multi-host process logs from what reaches it, like the
                # reference driver)
                shards = [np.asarray(s.data) for s in arr.addressable_shards]
                vals = np.mean(np.concatenate(shards, axis=0), axis=0)
            else:
                vals = np.asarray(jax.device_get(arr)).reshape(-1)
            for l in vals:
                self._loss_window.append(float(l))

    # ------------------------------------------------------------------
    # debug_info (reference: net.cpp:648-735, gated by
    # SolverParameter.debug_info) — per-blob mean-|x| tracing
    # ------------------------------------------------------------------
    def debug_info_pass(self, state: TrainState, batch, rng=None, log=None):
        """Log every blob's data / diff mean absolute value in the
        reference's ``[Forward]`` / ``[Backward]`` / ``[Update]`` line
        format.  One unjitted diagnostic pass (the reference pays this
        per iteration; here ``step`` runs it once per tau-window when
        ``debug_info`` is set — tracing inside the fused scan would
        serialize it)."""
        import sys

        log = log or (lambda s: print(s, file=sys.stderr))
        rng = rng if rng is not None else default_train_key(0)
        net = self.net

        def asum(x):
            x = jax.device_get(x)
            return float(jnp.mean(jnp.abs(jnp.asarray(x, jnp.float32))))

        out = net.apply(state.params, state.stats, batch, rng=rng, train=True)
        for b in net.feed_blobs:
            log(f"    [Forward] Input {b} data: {asum(batch[b]):.6g}")
        for layer in net.layers:
            for top in layer.lp.top:
                log(
                    f"    [Forward] Layer {layer.name}, top blob {top} "
                    f"data: {asum(out.blobs[top]):.6g}"
                )
            for pi, blob in enumerate(state.params.get(layer.name, [])):
                log(
                    f"    [Forward] Layer {layer.name}, param blob {pi} "
                    f"data: {asum(blob):.6g}"
                )

        # every activation gradient in one backward pass via zero taps
        taps = {
            name: jnp.zeros(shape, jnp.float32)
            for name, shape in net.blob_shapes.items()
            if name not in net.feed_blobs and name in out.blobs
        }

        def loss_fn(params, eps):
            return net.apply(
                params, state.stats, batch, rng=rng, train=True, perturb=eps
            ).loss

        param_g, tap_g = jax.grad(loss_fn, argnums=(0, 1))(
            state.params, taps
        )
        for layer in reversed(net.layers):
            for bot in layer.lp.bottom:
                if bot in tap_g:
                    log(
                        f"    [Backward] Layer {layer.name}, bottom blob "
                        f"{bot} diff: {asum(tap_g[bot]):.6g}"
                    )
            for pi in range(len(param_g.get(layer.name, []))):
                log(
                    f"    [Backward] Layer {layer.name}, param blob {pi} "
                    f"diff: {asum(param_g[layer.name][pi]):.6g}"
                )
        for layer in net.layers:
            for pi, blob in enumerate(state.params.get(layer.name, [])):
                log(
                    f"    [Update] Layer {layer.name}, param {pi} "
                    f"data: {asum(blob):.6g}; "
                    f"diff: {asum(param_g[layer.name][pi]):.6g}"
                )

    @property
    def smoothed_loss(self) -> float:
        """Windowed average (``average_loss``, solver.cpp:225-234).
        Reading this is the device->host sync point for pending loss
        arrays (see ``note_losses``)."""
        self._drain_losses()
        if not self._loss_window:
            return float("nan")
        return sum(self._loss_window) / len(self._loss_window)

    # ------------------------------------------------------------------
    # Test (TestAndStoreResult semantics)
    # ------------------------------------------------------------------
    def _forward_test(self, params, stats, batches, count=None):
        """Accumulate test-output sums over the leading batch axis.  When
        ``count`` is given (heterogeneous partitions: batches are padded to
        a common length), only the first ``count`` batches contribute — the
        pad-and-mask path that lets workers hold unequal test partition
        sizes (reference tolerates this via per-partition samplers,
        CifarApp.scala:103-106)."""

        def one(i, batch):
            if self.test_transform is not None:
                batch = self.test_transform(batch)
            blobs = self.test_net.forward(params, stats, batch)
            outs = {
                name: jnp.sum(blobs[name])
                for name in self._test_output_names()
            }
            if count is not None:
                w = (i < count).astype(jnp.float32)
                outs = {k: v * w for k, v in outs.items()}
            return i + 1, outs

        _, outs = jax.lax.scan(one, 0, batches)
        return {k: jnp.sum(v) for k, v in outs.items()}

    def _test_output_names(self) -> List[str]:
        produced = set()
        consumed = set()
        for layer in self.test_net.layers:
            produced.update(layer.lp.top)
            consumed.update(layer.lp.bottom)
        feed = set(self.test_net.feed_blobs)
        return sorted(produced - consumed - feed)

    def test_and_store_result(
        self, state: TrainState, batches: Dict[str, jax.Array]
    ) -> Dict[str, float]:
        """Forward ``num_test_batches`` (leading axis) through the TEST net
        sharing the train weights; return per-output *accumulated* scores —
        the driver divides by batch count, exactly like the reference
        (solver.cpp:413-444 + CifarApp.scala:113-115)."""
        out = self._jit_forward_test(state.params, state.stats, batches)
        return {k: float(v) for k, v in jax.device_get(out).items()}
