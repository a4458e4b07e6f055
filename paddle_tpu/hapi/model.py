"""paddle.Model — Keras-style high-level API.

Reference parity: python/paddle/hapi/model.py (unverified, mount empty):
prepare/fit/evaluate/predict/save/load + callbacks + metrics, dygraph
adapter semantics. TPU note: the eager step here is the correctness path;
``prepare(..., jit_compile=True)`` (default True once the step compiler
landed) swaps in a whole-step jitted trainer from paddle_tpu.jit for the
performance path.
"""
from __future__ import annotations

import os
import warnings
from collections.abc import Mapping as _Mapping

import numpy as np

from ..core.tensor import Tensor
from ..io import DataLoader
from ..metric.metrics import Metric
from . import callbacks as cbks_mod


def _to_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _tensorize(x):
    if isinstance(x, Tensor):
        return x
    import jax.numpy as jnp

    arr = np.asarray(x)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    return Tensor(jnp.asarray(arr))


class _LazyLogs(_Mapping):
    """Per-step logs whose values materialize on first READ.

    The fit hot loop must not synchronize with the device every step —
    a blocking read of a device value (``float(loss)``) is a full round
    trip that serializes the pipeline. Callbacks decide when values are
    actually needed (nothing reads under verbose=0; ProgBar's per-step
    handler is written to not touch the logs off its log_freq cadence),
    so the mapping drains the deferred metric updates and fetches the
    device loss only when someone looks.

    A ``Mapping`` rather than a dict subclass on purpose: ``dict(logs)``
    / ``{**logs}`` on a dict SUBCLASS take CPython's fast path that
    copies the raw storage without calling the overridden accessors —
    an unmaterialized snapshot would be silently empty. On a Mapping
    those constructions go through keys()/__getitem__ and materialize.
    """

    def __init__(self, drain):
        self._d = {}
        self._drain = drain

    def _mat(self):
        d, self._drain = self._drain, None
        if d is not None:
            d(self._d)

    def __getitem__(self, k):
        self._mat()
        return self._d[k]

    def __iter__(self):
        self._mat()
        return iter(self._d)

    def __len__(self):
        self._mat()
        return len(self._d)

    def __repr__(self):
        self._mat()
        return repr(self._d)


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self.stop_training = False
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._amp_level = None
        self._jit_step = None
        self._jit_enabled = False
        self._accumulating = False
        self._accumulate_grad_batches = 1
        self._pending_accum = False
        self._pending_metrics = []
        self._inputs_spec = _to_list(inputs) if inputs is not None else None
        self._labels_spec = _to_list(labels) if labels is not None else None

    # ------------------------------------------------------------- prepare
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, jit_compile=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            assert isinstance(m, Metric), f"metrics must be Metric, got {m}"
        if isinstance(amp_configs, str):
            self._amp_level = amp_configs
        elif isinstance(amp_configs, dict):
            self._amp_level = amp_configs.get("level", "O1")
        self._jit_enabled = bool(jit_compile)
        return self

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    # --------------------------------------------------------------- steps
    def _compute_loss(self, outputs, labels):
        outs = _to_list(outputs)
        lbls = _to_list(labels)
        if callable(self._loss):
            return self._loss(*(outs + lbls))
        raise RuntimeError("prepare() must be called with a loss for training")

    def train_batch(self, inputs, labels=None, update=True):
        # jit fast path shared with fit (_fit_step); this public entry
        # materializes eagerly — per-step floats are its contract
        res = self._fit_step(inputs, labels, update)
        if res is not None:
            loss, outputs, lbls = res
            metrics = []
            for m in self._metrics:
                m_in = m.compute(*(_to_list(outputs) + lbls))
                metrics.append(m.update(*_to_list(m_in)))
            out_loss = [float(np.asarray(loss.numpy()))]
            return (out_loss, metrics) if metrics else out_loss

        import time

        _t0 = time.perf_counter()
        self.network.train()
        inputs = [_tensorize(x) for x in _to_list(inputs)]
        labels = [_tensorize(y) for y in _to_list(labels)]

        from ..amp import auto_cast

        with auto_cast(enable=self._amp_level in ("O1", "O2"),
                       level=self._amp_level or "O1"):
            outputs = self.network(*inputs)
            loss = self._compute_loss(outputs, labels)
        if self._accumulating:
            # average (not sum) over the accumulation window
            (loss / float(self._accumulate_grad_batches)).backward()
        else:
            loss.backward()
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
            self._pending_accum = False
            self._accum_count = 0
            # training telemetry (eager path; the jit path meters inside
            # CompiledTrainStep). Loss stays a device ref — the meter's
            # lazy gauge fetches it on scrape, not here.
            try:
                from .. import observability as obs

                meter = obs.get_step_meter()
                meter.auto_configure(self.network)
                examples, tokens = obs.batch_geometry(
                    [getattr(x, "value", x) for x in inputs]
                )
                meter.observe_step(
                    time.perf_counter() - _t0, examples=examples,
                    tokens=tokens, loss=loss.value,
                )
            except Exception:
                pass
        elif self._accumulating:
            self._pending_accum = True
            self._accum_count = getattr(self, "_accum_count", 0) + 1
        metrics = []
        for m in self._metrics:
            m_in = m.compute(*(_to_list(outputs) + labels))
            metrics.append(m.update(*_to_list(m_in)))
        out_loss = [float(np.asarray(loss.numpy()))]
        return (out_loss, metrics) if metrics else out_loss

    def _jit_train_batch(self, inputs, labels):
        """Whole-step compiled path; falls back to eager when unsupported."""
        if self._jit_step is None:
            try:
                from ..jit.trainer import CompiledTrainStep

                self._jit_step = CompiledTrainStep(
                    self.network, self._compute_loss_fn(), self._optimizer,
                    amp_level=self._amp_level,
                )
            except NotImplementedError:
                self._jit_enabled = False
                return None, None
            s = getattr(self, "_sentinel", None)
            if s is not None:
                self._jit_step.attach_sentinel(s)
            w = getattr(self, "_watchdog", None)
            if w is not None:
                w.attach(self._jit_step)
        loss, outputs = self._jit_step(inputs, labels)
        return outputs, loss

    def _compute_loss_fn(self):
        loss = self._loss
        if not callable(loss):
            raise NotImplementedError("jit path requires a callable loss")
        return loss

    # ------------------------------------------------- fit fast path
    # Deferred-sync stepping: the compiled step is dispatched, metric
    # inputs stay as device refs, and nothing fetches from the device
    # until a callback reads the logs (or the pending window fills /
    # the epoch ends). Device compute, the next batch's host->device
    # transfer, and the DataLoader's collation all overlap.
    _PENDING_MAX = 64  # drain bound: caps device refs held per window

    def _fit_step(self, inputs, labels, update):
        """Sync-free step for fit's hot loop. Returns (loss_dev,
        outputs, labels) or None when the batch must go through the
        eager train_batch (accumulation, jit off, jit fallback)."""
        if not (self._jit_enabled and update and not self._accumulating):
            return None
        self.network.train()
        inputs = [_tensorize(x) for x in _to_list(inputs)]
        labels = [_tensorize(y) for y in _to_list(labels)]
        outputs, loss = self._jit_train_batch(inputs, labels)
        if outputs is None:
            return None  # jit unsupported: caller reruns eagerly
        return loss, outputs, labels

    def _drain_pending_metrics(self):
        pending, self._pending_metrics = self._pending_metrics, []
        for outputs, labels in pending:
            for m in self._metrics:
                m_in = m.compute(*(_to_list(outputs) + labels))
                m.update(*_to_list(m_in))

    def _lazy_logs(self, loss):
        def drain(d):
            self._drain_pending_metrics()
            d["loss"] = float(np.asarray(loss.numpy()))
            for m in self._metrics:
                n, val = m.name(), m.accumulate()
                if isinstance(n, list):
                    vals = val if isinstance(val, list) else [val]
                    for nn, vv in zip(n, vals):
                        d[nn] = vv
                else:
                    d[n] = val

        return _LazyLogs(drain)

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        inputs = [_tensorize(x) for x in _to_list(inputs)]
        labels = [_tensorize(y) for y in _to_list(labels)]
        outputs = self.network(*inputs)
        metrics = []
        losses = []
        if self._loss is not None and labels:
            loss = self._compute_loss(outputs, labels)
            losses = [float(np.asarray(loss.numpy()))]
        for m in self._metrics:
            m_in = m.compute(*(_to_list(outputs) + labels))
            metrics.append(m.update(*_to_list(m_in)))
        return (losses, metrics) if metrics else losses

    def predict_batch(self, inputs):
        self.network.eval()
        inputs = [_tensorize(x) for x in _to_list(inputs)]
        outputs = self.network(*inputs)
        return [o.numpy() for o in _to_list(outputs)]

    # ----------------------------------------------------------------- fit
    def _make_loader(self, data, batch_size, shuffle, num_workers, drop_last):
        if data is None or isinstance(data, DataLoader):
            return data
        return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                          num_workers=num_workers, drop_last=drop_last)

    def _split_batch(self, batch):
        if isinstance(batch, (list, tuple)):
            if len(batch) >= 2:
                return list(batch[:-1]), [batch[-1]]
            return [batch[0]], []
        return [batch], []

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, checkpoint=None,
            sentinel=None):
        assert train_data is not None
        if checkpoint is not None or sentinel is not None:
            cb = callbacks if isinstance(callbacks, (list, tuple)) else (
                [callbacks] if callbacks is not None else []
            )
            callbacks = list(cb)
        if checkpoint is not None:
            # fault-tolerant path: a checkpoint.CheckpointManager rides
            # the callback stream (per-step policy, async atomic saves,
            # drained at train end)
            callbacks.append(
                cbks_mod.FaultTolerantCheckpoint(checkpoint)
            )
        if sentinel is not None:
            # resilience path: a training.AnomalySentinel attaches to
            # the compiled step; a rollback inside fit continues with
            # the NEXT batch (a loader cannot rewind — see
            # callbacks.ResilientTraining for the semantics)
            callbacks.append(cbks_mod.ResilientTraining(sentinel))
        loader = self._make_loader(train_data, batch_size, shuffle,
                                   num_workers, drop_last)
        eval_loader = self._make_loader(eval_data, batch_size, False,
                                        num_workers, False)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, batch_size=batch_size, epochs=epochs,
            steps=steps, log_freq=log_freq, verbose=verbose,
            save_freq=save_freq, save_dir=save_dir, metrics=self._metrics_name(),
        )
        self.stop_training = False
        self._accumulating = accumulate_grad_batches > 1
        self._accumulate_grad_batches = max(1, accumulate_grad_batches)
        self._pending_accum = False
        self._accum_count = 0
        cbks.on_train_begin()
        it = 0
        self._pending_metrics = []
        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch)
            for m in self._metrics:
                m.reset()
            logs = {}
            accum = 0
            for step, batch in enumerate(loader):
                cbks.on_train_batch_begin(step)
                inputs, labels = self._split_batch(batch)
                accum += 1
                update = accum % max(1, accumulate_grad_batches) == 0
                try:
                    res = self._fit_step(inputs, labels, update)
                except Exception as e:
                    from ..training.resilience import RollbackAndReplay

                    if isinstance(e, RollbackAndReplay):
                        # rollback-without-replay: params/optimizer/RNG
                        # are back at the last commit; the loader can't
                        # rewind, so continue with the next batch
                        continue
                    raise
                if res is not None:
                    loss, outputs, lbls = res
                    if self._metrics:
                        self._pending_metrics.append((outputs, lbls))
                        if len(self._pending_metrics) >= self._PENDING_MAX:
                            self._drain_pending_metrics()
                    logs = self._lazy_logs(loss)
                else:
                    out = self.train_batch(inputs, labels, update=update)
                    logs = self._merge_logs(out)
                cbks.on_train_batch_end(step, logs)
                it += 1
                if num_iters is not None and it >= num_iters:
                    self.stop_training = True
                    break
            if self._pending_accum:
                # flush a trailing partial accumulation window so its
                # grads don't leak into the next epoch's first update.
                # Losses were scaled by 1/N but only k<N batches landed;
                # rescale grads by N/k so the flush is a true average.
                k = max(1, getattr(self, "_accum_count", 1))
                n = self._accumulate_grad_batches
                if k < n:
                    rescale = float(n) / float(k)
                    for p in self.network.parameters():
                        if p.grad is not None:
                            p.grad.value = p.grad.value * rescale
                self._optimizer.step()
                self._optimizer.clear_grad()
                self._pending_accum = False
                self._accum_count = 0
            if isinstance(logs, _LazyLogs):
                logs._mat()  # epoch boundary: flush metrics + fetch loss
            else:
                self._drain_pending_metrics()
            cbks.on_epoch_end(epoch, logs)
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                self._run_eval(eval_loader, cbks)
            if self.stop_training:
                break
        cbks.on_train_end(logs)
        # accumulation is a per-fit setting; a later direct train_batch()
        # must not inherit the 1/N loss scaling
        self._accumulating = False
        self._accumulate_grad_batches = 1
        return self

    def _metrics_name(self):
        names = ["loss"]
        for m in self._metrics:
            n = m.name()
            names += n if isinstance(n, list) else [n]
        return names

    def _merge_logs(self, out):
        logs = {}
        if isinstance(out, tuple):
            losses, metrics = out
        else:
            losses, metrics = out, []
        if losses:
            logs["loss"] = losses[0] if len(losses) == 1 else losses
        for m, val in zip(self._metrics, metrics):
            n = m.name()
            if isinstance(n, list):
                vals = val if isinstance(val, list) else [val]
                for nn, vv in zip(n, vals):
                    logs[nn] = vv
            else:
                logs[n] = val
        return logs

    def _run_eval(self, eval_loader, cbks):
        cbks.on_eval_begin()
        for m in self._metrics:
            m.reset()
        logs = {}
        loss_sum, n_total = 0.0, 0
        for step, batch in enumerate(eval_loader):
            cbks.on_eval_batch_begin(step)
            inputs, labels = self._split_batch(batch)
            out = self.eval_batch(inputs, labels)
            logs = self._merge_logs(out)
            n = (
                inputs[0].shape[0]
                if inputs and hasattr(inputs[0], "shape") and inputs[0].shape
                else 1
            )
            if "loss" in logs:
                loss_sum += float(logs["loss"]) * n
                n_total += n
            cbks.on_eval_batch_end(step, logs)
        final = {}
        if n_total:
            # sample-weighted mean over the dataset (not the last batch)
            final["loss"] = loss_sum / n_total
        for m in self._metrics:
            n = m.name()
            acc = m.accumulate()
            if isinstance(n, list):
                accs = acc if isinstance(acc, list) else [acc]
                final.update(dict(zip(n, accs)))
            else:
                final[n] = acc
        cbks.on_eval_end(final)
        return final

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        loader = self._make_loader(eval_data, batch_size, False, num_workers,
                                   False)
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, batch_size=batch_size, verbose=verbose,
            log_freq=log_freq, metrics=self._metrics_name(), mode="eval",
        )
        return self._run_eval(loader, cbks)

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False,
                verbose=1, callbacks=None):
        loader = self._make_loader(test_data, batch_size, False, num_workers,
                                   False)
        outputs = []
        for batch in loader:
            inputs, _ = (
                self._split_batch(batch)
                if isinstance(batch, (list, tuple)) and len(batch) > 1
                else (_to_list(batch), [])
            )
            outputs.append(self.predict_batch(inputs))
        # transpose [steps][n_out] -> [n_out][steps]
        grouped = list(zip(*outputs))
        if stack_outputs:
            return [np.concatenate(g, axis=0) for g in grouped]
        return [list(g) for g in grouped]

    # ------------------------------------------------------------ save/load
    def save(self, path, training=True):
        from ..framework.io import save as fsave

        if not training:
            from .. import jit

            jit.save(self.network, path, input_spec=self._inputs_spec)
            return
        fsave(self.network.state_dict(), path + ".pdparams")
        if self._optimizer is not None:
            fsave(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load as fload

        state = fload(path + ".pdparams" if not path.endswith(".pdparams") else path)
        missing, unexpected = self.network.set_state_dict(state)
        if (missing or unexpected) and not skip_mismatch:
            if missing:
                warnings.warn(f"missing keys in checkpoint: {missing}")
            if unexpected:
                warnings.warn(f"unexpected keys in checkpoint: {unexpected}")
        opt_path = path + ".pdopt"
        if (
            not reset_optimizer
            and self._optimizer is not None
            and os.path.exists(opt_path)
        ):
            self._optimizer.set_state_dict(fload(opt_path))

    def summary(self, input_size=None, dtype=None):
        lines = [repr(self.network)]
        total = sum(p.size for p in self.network.parameters())
        trainable = sum(
            p.size for p in self.network.parameters() if not p.stop_gradient
        )
        lines.append(f"Total params: {total}")
        lines.append(f"Trainable params: {trainable}")
        s = "\n".join(lines)
        print(s)
        return {"total_params": total, "trainable_params": trainable}
