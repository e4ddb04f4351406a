"""Path `train_fused`: a gluon model-zoo network trained through
`gluon.contrib.FusedTrainStep`, fed by `mx.io.DeviceFeed`.

Set-up builds ONE step object, drives it through its first steps on the
feed's first batches (keeping the optimizer's state after step 1 and the
parameters after step 3 on the host), and hands that same object and feed
to the measured window. The plain reference follows the first three steps
after the window has closed and the program's state is freed."""
from __future__ import annotations

import collections
import gc
import time

from .. import checks, traffic, weights, work
from ..memory import peak_bytes
from ..reference import resnet as reference

FIRST_STEPS = 3


class Program:
    """The system under test: net, fused step, feed, from the seed."""

    def __init__(self, cfg, tr, seed):
        import numpy as np
        import incubator_mxnet_tpu as mx
        from incubator_mxnet_tpu import amp, gluon
        from incubator_mxnet_tpu import optimizer as opt_mod
        from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
        from incubator_mxnet_tpu.gluon.model_zoo import vision

        m, opt, prog = cfg["model"], cfg["optimizer"], cfg["program"]
        self.cfg, self.tr, self.m = cfg, tr, m
        self.batch = tr["batch"]
        shapes = weights.resnet_shapes(m)
        self.names = sorted(n for n, (_, kind) in shapes.items()
                            if weights.trainable(kind))
        mx.seed(seed & 0x7FFFFFFF)
        self._amp = amp if prog.get("amp") else None
        if self._amp:
            amp.init(prog["amp"])
        made = weights.resnet_params(m, seed)
        net = getattr(vision, prog["model_zoo"])(layout=prog["layout"],
                                                 classes=m["classes"])
        net.initialize()
        net.hybridize()
        hw = m["input_hw"]
        net(mx.np.zeros((1, hw, hw, m["in_channels"]), dtype="float32"))
        self.params = net.collect_params()
        if sorted(self.params) != sorted(shapes):
            raise RuntimeError(
                "the model zoo's leaves are not the reference's: "
                f"{sorted(set(self.params) ^ set(shapes))[:6]}")
        for name, p in self.params.items():
            if tuple(p.shape) != tuple(shapes[name][0]):
                raise RuntimeError(f"{name}: {p.shape} vs {shapes[name][0]}")
            p.set_data(mx.np.array(np.asarray(made[name])))
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

        def objective(n, xb, yb):
            return loss_fn(n(xb), yb).sum()

        self.step = FusedTrainStep(
            net, objective,
            opt_mod.create("sgd", learning_rate=opt["learning_rate"],
                           momentum=opt["momentum"], wd=opt["wd"],
                           rescale_grad=1.0 / self.batch),
            steps_per_call=prog["steps_per_call"], remat=None, donate=True,
            use_fusion=prog["use_fusion"])
        self.pool = traffic.host_batches(tr, seed, m)
        self.feed = mx.io.DeviceFeed(traffic.cycle(self.pool),
                                     depth=tr["feed_depth"])
        self.batches = iter(self.feed)
        self.pending = collections.deque()

    def one(self):
        """One step through the window's own call and feed; keeps at most
        `in_flight` steps undone behind it."""
        loss = self.step(*next(self.batches))
        self.pending.append(loss)
        if len(self.pending) > self.tr["in_flight"]:
            self.pending.popleft().wait_to_read()
        return loss

    def first_steps(self):
        """Drives the first steps; returns their losses, the first
        gradient as the optimizer got it and the parameters after them,
        as host arrays by leaf."""
        import numpy as np
        lr = np.float32(self.cfg["optimizer"]["learning_rate"])
        losses = [float(self.one().asnumpy())]
        # SGD's momentum after one step from zero is -lr * rescaled grad
        first_grad = {n: -np.asarray(s.asnumpy(), np.float32) / lr
                      for n, s in zip(self.names, self.step._states)}
        for _ in range(FIRST_STEPS - 1):
            losses.append(float(self.one().asnumpy()))
        after = {n: np.asarray(self.params[n].data().asnumpy(), np.float32)
                 for n in self.names}
        return losses, first_grad, after

    def drain(self):
        while self.pending:
            self.pending.popleft().wait_to_read()

    def close(self):
        self.feed.close()
        if self._amp:
            self._amp.uninit()
        self.step = self.params = self.feed = self.batches = None
        self.pending.clear()
        gc.collect()


def reference_steps(cfg, seed, pool, names, **how):
    """The plain reference over the first steps' batches -> (losses,
    first gradient, change of the parameters), host arrays by leaf."""
    import numpy as np
    import jax
    m = cfg["model"]
    run_ref = reference.make_sgd_steps(m, cfg["optimizer"], **how)
    keep = set(names)
    w0 = {n: v for n, v in weights.resnet_params(m, seed).items()
          if n in keep}
    xs = np.stack([pool[i % len(pool)][0] for i in range(FIRST_STEPS)])
    ys = np.stack([pool[i % len(pool)][1] for i in range(FIRST_STEPS)])
    ref = jax.device_get(run_ref(w0, xs, ys))
    w0 = jax.device_get(w0)
    return ([float(v) for v in ref["losses"]], ref["first_grad"],
            {n: ref["params"][n] - w0[n] for n in names}, w0)


def run(ctx):
    import numpy as np
    import jax
    from incubator_mxnet_tpu import profiler

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    m, prog = cfg["model"], cfg["program"]
    tracer = ctx["tracer"]
    p = Program(cfg, tr, seed)
    try:
        losses, first_grad, after = p.first_steps()
        for _ in range(tr["lead_in_steps"]):
            p.one()
        p.drain()

        # -- the window ---------------------------------------------------
        feed0 = profiler.feed_stats()["stall_data_us"]
        t0 = time.perf_counter()
        setup_s = t0 - ctx["t_process_start"]
        steps = 0

        def mark():
            return {"steps": steps,
                    "stall_us": profiler.feed_stats()["stall_data_us"]}

        while True:
            loss = p.one()
            steps += 1
            now = time.perf_counter() - t0
            if tracer.due(now):
                # whole steps inside the interval: nothing in flight at
                # its ends
                p.drain()
                tracer.toggle(mark())
            if now >= ctx["seconds"]:
                break
        p.drain()
        window_s = time.perf_counter() - t0
        tracer.finish(mark())
        stall_s = (profiler.feed_stats()["stall_data_us"] - feed0) * 1e-6
        last_loss = float(loss.asnumpy())
        peak = peak_bytes(jax.devices()[0])
        pool, names, batch = p.pool, p.names, p.batch
    finally:
        p.close()
    del p, loss

    # -- the reference follows the first three steps ----------------------
    t_ref = time.perf_counter()
    ref_losses, ref_grad, ref_change, w0 = reference_steps(
        cfg, seed, pool, names)
    compared = checks.training(
        losses, first_grad, {n: after[n] - w0[n] for n in names},
        ref_losses, ref_grad, ref_change)
    reference_s = time.perf_counter() - t_ref

    per_step = batch * prog["steps_per_call"]
    counters = {"steps": steps, "batch": batch, "reference_s": reference_s,
                "images_per_s": steps * per_step / window_s,
                "first_losses": " ".join(f"{v:.6g}" for v in losses),
                "loss_gaps": " ".join(f"{v:.3g}" for v in checks.loss_gaps(
                    losses, ref_losses))}
    if tracer.traced():
        # per-layer metrics read the traced interval: counters and clock
        # between the two marks, the profiler's start and stop left out
        a, b = tracer.marks
        images = (b["steps"] - a["steps"]) * per_step
        counters.update(
            interval_s=tracer.interval_s(),
            feed_stall_data_s=(b["stall_us"] - a["stall_us"]) * 1e-6,
            useful_flops=images * work.resnet_train_flops_per_image(m),
            fused_apply_flops=0,
            fused_apply_bytes=images
            * work.resnet_bn_apply_bytes_per_image(m))
    else:
        counters["feed_stall_data_s"] = stall_s
    return {
        "e2e": {"train_step_ms": 1e3 * window_s / steps, "setup_s": setup_s},
        "attempted": steps,
        "failed": 0 if np.isfinite(last_loss) else steps,
        "compared": compared,
        "memory_peak_bytes": peak, "counters": counters,
    }
