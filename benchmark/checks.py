"""The comparisons that decide ``correct``, each made outside the window.

A check returns ``{name: bool}``; the numbers behind each verdict go to an
earlier line of the output through ``cell.log``.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import plain_ops


def rel_err(got, want):
    """Relative L2 error, in float64 on the host."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def transform_is_a_crop(frames, mean, out, crop):
    """Whether every transformed image is one ``crop`` x ``crop`` window of
    its stored frame minus the same window of the mean, mirrored or not.  The
    window is found from the image itself, so the check does not depend on
    how the program draws its offsets."""
    full = frames.astype(np.float32) - mean
    last = full.shape[-1] - crop
    for image, target in zip(full, np.asarray(out, np.float32)):
        found = False
        for t in (target, target[:, :, ::-1]):
            rows = np.lib.stride_tricks.sliding_window_view(
                image[0], crop, axis=1
            )  # (S, S - crop + 1, crop): every candidate first row
            hit = np.all(np.abs(rows - t[0, 0]) < 1e-3, axis=-1)
            for h, w in zip(*np.nonzero(hit[: last + 1])):
                window = image[:, h:h + crop, w:w + crop]
                found |= bool(np.all(np.abs(window - t) < 1e-3))
        if not found:
            return False
    return True


def step_against_reference(cell):
    """One forward and backward pass of the program's net on a seeded batch,
    at the published widths, against the configuration's plain reference.

    Twice: the net in float32 under ``default_matmul_precision("highest")``,
    which must agree to summation order (so a dropped or altered term fails),
    and the net in the configuration's compute dtype, which must agree to that
    dtype's rounding (so a lower precision than the file states fails).
    Dropout is set to ratio 0 in the check's copy of the net description."""
    from sparknet_tpu import config as cfg
    from sparknet_tpu.solver import Solver

    config, spec = cell.config, cell.config["check"]
    ref = importlib.import_module("benchmark.reference." + config["reference"])
    n, crop = spec["batch"], cell.crop
    frames, labels = cell.sample_frames(n)
    batch = jax.jit(cell.solver.train_transform)(
        {"data": frames, "label": labels}, jax.random.key(cell.seed)
    )
    x = np.asarray(batch["data"])
    verdict = {
        "transform_is_a_crop": transform_is_a_crop(frames, cell.mean, x, crop)
    }

    shard0 = lambda a: a.addressable_shards[0].data[0]  # noqa: E731
    params = jax.tree_util.tree_map(shard0, cell.state.params)
    stats = jax.tree_util.tree_map(shard0, cell.state.stats)
    probes = [ref.FIRST_CONV, ref.LAST_FC]
    want = plain_ops.step(ref.logits, params, x, labels, probes)
    want = [want[0], want[1], *want[2]]
    cell.reference_loss = float(want[0])

    shapes = [(n, 3, crop, crop), (n,)]
    net_param = cfg.replace_data_layers(cell.net_param, shapes, shapes)
    for layer in net_param.layer:
        if layer.type == "Dropout":
            layer.dropout_param.dropout_ratio = 0.0

    def system(dtype):
        net = Solver(
            cell.solver_param, net_param=net_param, compute_dtype=dtype
        ).net

        def step(params, stats, x, labels):
            (loss, (blobs, _)), grads = jax.value_and_grad(
                net.loss_fn, has_aux=True
            )(params, stats, {"data": x, "label": labels},
              jax.random.key(0), True)
            return [loss, blobs[ref.LAST_FC].astype(jnp.float32),
                    grads[ref.FIRST_CONV][0], grads[ref.LAST_FC][0]]

        return jax.jit(step)(params, stats, x, labels)

    names = ["loss", "logits", "grad_first_conv", "grad_last_fc"]
    with jax.default_matmul_precision("highest"):
        exact = [rel_err(g, w) for g, w in zip(system(None), want)]
    stated = [
        rel_err(g, w)
        for g, w in zip(system(config["compute_dtype"]), want)
    ]
    cell.log("step against the plain reference, relative L2 error: "
             f"float32/highest {dict(zip(names, exact))}, "
             f"{config['compute_dtype']} {dict(zip(names, stated))}, "
             f"reference loss {cell.reference_loss:.4f}")
    def within(errs, tol):
        # a quantity without a bound in the file is printed and not judged
        return all(
            err <= tol[name] for name, err in zip(names, errs) if name in tol
        )

    verdict["reference_exact"] = within(exact, spec["exact_rel_tol"])
    verdict["reference_stated_dtype"] = within(stated, spec["rel_tol"])
    return verdict


def averaging_across_workers(cell):
    """What exists only across chips: after a round with every worker live,
    all workers hold bit-equal parameters, every params / history / batch leaf
    sits on as many distinct devices as there are workers, and the averaged
    parameters are the mean of what the same compiled round gives from the
    same start when only worker w is live, over all w (the mask is an array
    argument: five rounds, no compile)."""
    trainer, w = cell.trainer, cell.workers
    leaves = jax.tree_util.tree_leaves

    @jax.jit
    def copy(tree):
        return jax.tree_util.tree_map(jnp.copy, tree)

    @jax.jit
    def spread(params):
        """Largest difference between any worker's parameters and worker 0's."""
        return jnp.max(jnp.stack(
            [jnp.max(jnp.abs(x - x[:1])) for x in leaves(params)]
        ))

    @jax.jit
    def add(acc, params):
        return jax.tree_util.tree_map(jnp.add, acc, params)

    @jax.jit
    def mean_err(avg, acc):
        """max |avg - acc / W| over max |avg|, worker 0's slot."""
        pairs = list(zip(leaves(avg), leaves(acc)))
        err = jnp.max(jnp.stack(
            [jnp.max(jnp.abs(a[0] - s[0] / w)) for a, s in pairs]
        ))
        top = jnp.max(jnp.stack([jnp.max(jnp.abs(a[0])) for a, _ in pairs]))
        return err / top

    def devices_of(tree):
        return min(
            len({s.device for s in leaf.addressable_shards})
            for leaf in leaves(tree)
        )

    batch = cell.repeatable_batch()
    placed = min(devices_of(t) for t in
                 (cell.state.params, cell.state.history, batch))
    # one round at a time, each awaited: what is alive stays small and the same
    full, _ = jax.block_until_ready(
        trainer.round(copy(cell.state), batch, round_index=0))
    full, acc = full.params, None
    for i in range(w):
        mask = np.zeros((w,), np.float32)
        mask[i] = 1.0
        alone, _ = jax.block_until_ready(trainer.round(
            copy(cell.state), cell.repeatable_batch(),
            live_mask=mask, round_index=0,
        ))
        acc = alone.params if acc is None else add(acc, alone.params)
        del alone
    workers_apart = float(spread(full))
    off_mean = float(mean_err(full, acc))
    cell.log(f"averaging across {w} workers: leaves on {placed} devices, "
             f"max |p_w - p_0| after the round {workers_apart}, "
             f"|average - mean of one-hot rounds| / max|p| {off_mean:.3g}")
    return {
        "workers_bit_equal": workers_apart == 0.0,
        "leaves_on_every_device": placed == w,
        "average_is_mean_of_workers": off_mean <= 1e-6,
    }
