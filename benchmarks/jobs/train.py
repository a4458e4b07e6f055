"""Job ``train``: whole-step compiled training, on one chip or (a cell
with ``parallel``) through the Fleet hybrid path across chips.

``CompiledTrainStep`` (the optimizer and AMP level the cell names) on
the cell's configuration, fresh seeded batches every step, steps
dispatched back to back with the loss read (blocking) every
``read_every``-th step, as a job that logs does. The window ends at the
first such read after ``--seconds``, so every counted step has finished
and ``train_tok_s`` is all the tokens over all the time.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import flops, harness, loadgen


def run(ctx):
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    cfg, cell, mix = ctx.config, ctx.cell, ctx.mix
    tr = cell["trainer"]
    batch, seq, vocab = int(mix["batch"]), int(mix["seq"]), cfg["vocab_size"]
    read_every = int(tr.get("read_every", 10))
    paddle.seed(ctx.seed)
    harness.note("train: building the net and the batches")
    if cell.get("parallel"):
        net, place = ctx.builder.build_hybrid(cfg, ctx.seed, cell["parallel"])
    else:
        net, _ = ctx.builder.build(cfg, ctx.seed,
                                   tr.get("param_dtype", "float32"))
        place = lambda a: a
    pool = loadgen.plan_train_batches(
        {**mix, "seq": seq + 1}, ctx.seed, vocab)
    @jax.jit
    def take(p, i):     # inputs, and the token after each as its label
        b = jax.lax.dynamic_index_in_dim(p, i, 0, False)
        return b[:, :-1], b[:, 1:]

    def batch_at(i):
        x, y = take(pool, np.int32(i % pool.shape[0]))
        return paddle.Tensor(place(x)), paddle.Tensor(place(y))

    # the reference first: it never shares the chip with optimizer state
    x0, y0 = batch_at(0)
    harness.note("train: reference loss on the first batch")
    ref_loss = ctx.reference.loss(ctx.builder.weights(net), cfg,
                                  x0.value, y0.value)

    def loss_fn(logits, labels):
        if cell.get("parallel"):    # the vocab-parallel loss seam
            return net._loss_fn(logits, labels)
        return F.cross_entropy(logits.reshape([-1, vocab]),
                               labels.reshape([-1]))

    opt = getattr(paddle.optimizer, tr["optimizer"])(
        float(tr["learning_rate"]), parameters=net.parameters())
    step = paddle.jit.CompiledTrainStep(
        net, loss_fn, opt, amp_level=tr.get("amp_level"),
        amp_dtype=tr.get("amp_dtype", "bfloat16"))

    harness.note("train: first step (compiles)")
    # only the loss is kept of a step's outputs: the logits it also
    # returns are dropped before the next step, as a training job does
    loss = step([x0], [y0])[0]
    first_loss = float(loss.numpy())
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    ok = math.isfinite(first_loss) and rel <= ctx.reference.TRAIN_LOSS_RTOL
    harness.line("check", first_loss=first_loss, reference_loss=ref_loss,
                 rel_diff=rel, allowed=ctx.reference.TRAIN_LOSS_RTOL, ok=ok)
    done = 1 + int(tr.get("warm_steps", 4))
    for i in range(1, done):
        loss = step(*[[t] for t in batch_at(i)])[0]
    float(loss.numpy())

    # ---- the window
    compiles0 = ctx.compiles.compiles
    tw = harness.TraceWindow(ctx.root, ctx.name) if ctx.trace else None
    traced_spans, spans, losses = 0, [], []
    w0 = last = ctx.window_opens()
    n = 0
    while True:
        loss = step(*[[t] for t in batch_at(done + n)])[0]
        losses.append(loss)
        n += 1
        if n % read_every:
            continue
        float(loss.numpy())                     # blocks on the step
        now = time.perf_counter()
        over = now - w0 >= ctx.seconds
        if tw is not None and tw.active:
            # the profiler spans two reads' worth of steps, and its
            # spans stay out of the step clock
            traced_spans += 1
            if traced_spans == 2 or over:
                tw.stop()
        else:
            spans.append((read_every, now - last))
        if over:
            break
        if tw is not None and tw.t0 is None and now - w0 >= ctx.seconds / 3.0:
            tw.start()
        last = time.perf_counter()
    w1 = time.perf_counter()
    values = np.array([float(l.numpy()) for l in losses])
    failed = int((~np.isfinite(values)).sum())
    compiled_in_window = ctx.compiles.compiles - compiles0
    tok_s = n * batch * seq / (w1 - w0)
    fl_tok = flops.train_flops_per_token(cfg, seq)
    harness.line(
        "train", steps=n, window_s=w1 - w0, tokens_per_step=batch * seq,
        step_ms_median=float(np.median([1e3 * s / k for k, s in spans])),
        spans=len(spans), loss_first=float(values[0]),
        loss_last=float(values[-1]), compiles_in_window=compiled_in_window,
        flops_per_token=fl_tok, matmul_params=flops.matmul_params(cfg),
        mfu=tok_s * fl_tok / (ctx.peaks["flops_bf16"] * len(ctx.devices)))
    return {
        "correct": bool(ok and failed == 0 and compiled_in_window == 0),
        "attempted": n, "failed": failed,
        "end_to_end": {"train_tok_s": tok_s},
        "obs": {"step_clock": spans,
                "work": {"train_flops_per_step":
                         flops.train_flops_per_step(cfg, batch, seq)}},
        "trace": tw,
    }
