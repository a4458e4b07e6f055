"""hapi callbacks.

Reference parity: python/paddle/hapi/callbacks.py (unverified, mount empty):
Callback/CallbackList, ProgBarLogger, ModelCheckpoint, EarlyStopping,
LRScheduler, VisualDL (no-op stub here — visualdl is not in the image).
"""
from __future__ import annotations

import numbers
import os
import sys
import time


class Callback:
    def __init__(self):
        self.model = None
        self.params = {}

    def set_params(self, params):
        self.params = params or {}

    def set_model(self, model):
        self.model = model

    def on_train_begin(self, logs=None):
        pass

    def on_train_end(self, logs=None):
        pass

    def on_eval_begin(self, logs=None):
        pass

    def on_eval_end(self, logs=None):
        pass

    def on_predict_begin(self, logs=None):
        pass

    def on_predict_end(self, logs=None):
        pass

    def on_epoch_begin(self, epoch, logs=None):
        pass

    def on_epoch_end(self, epoch, logs=None):
        pass

    def on_train_batch_begin(self, step, logs=None):
        pass

    def on_train_batch_end(self, step, logs=None):
        pass

    def on_eval_batch_begin(self, step, logs=None):
        pass

    def on_eval_batch_end(self, step, logs=None):
        pass

    def on_predict_batch_begin(self, step, logs=None):
        pass

    def on_predict_batch_end(self, step, logs=None):
        pass


class CallbackList:
    def __init__(self, callbacks):
        self.callbacks = list(callbacks)

    def set_params(self, params):
        for c in self.callbacks:
            c.set_params(params)

    def set_model(self, model):
        for c in self.callbacks:
            c.set_model(model)

    def __getattr__(self, name):
        if name.startswith("on_"):
            def dispatch(*args, **kwargs):
                for c in self.callbacks:
                    getattr(c, name)(*args, **kwargs)

            return dispatch
        raise AttributeError(name)


def _fmt(v):
    if isinstance(v, numbers.Number):
        return f"{v:.4f}"
    if isinstance(v, (list, tuple)):
        return "[" + ", ".join(_fmt(x) for x in v) + "]"
    return str(v)


class ProgBarLogger(Callback):
    def __init__(self, log_freq=1, verbose=2):
        super().__init__()
        self.log_freq = log_freq
        self.verbose = verbose

    def on_train_begin(self, logs=None):
        self.epochs = self.params.get("epochs")
        self._t0 = time.time()

    def on_epoch_begin(self, epoch, logs=None):
        self.epoch = epoch
        self.steps = self.params.get("steps")
        self._step = 0
        if self.verbose and self.epochs:
            print(f"Epoch {epoch + 1}/{self.epochs}")

    def on_train_batch_end(self, step, logs=None):
        # NOTE: no `logs or {}` here — truth-testing materializes a
        # lazy logs mapping (device sync); only touch it ON the
        # log_freq cadence so the sync-free fit path stays sync-free
        self._step += 1
        if self.verbose and self._step % self.log_freq == 0:
            items = " - ".join(
                f"{k}: {_fmt(v)}" for k, v in (logs or {}).items()
            )
            total = self.steps if self.steps is not None else "?"
            print(f"step {self._step}/{total} - {items}")
            sys.stdout.flush()

    def on_epoch_end(self, epoch, logs=None):
        logs = logs or {}
        if self.verbose:
            items = " - ".join(f"{k}: {_fmt(v)}" for k, v in logs.items())
            print(f"Epoch {epoch + 1} done - {items}")

    def on_eval_begin(self, logs=None):
        if self.verbose:
            print("Eval begin...")

    def on_eval_end(self, logs=None):
        logs = logs or {}
        if self.verbose:
            items = " - ".join(f"{k}: {_fmt(v)}" for k, v in logs.items())
            print(f"Eval done - {items}")


class ModelCheckpoint(Callback):
    def __init__(self, save_freq=1, save_dir=None):
        super().__init__()
        self.save_freq = save_freq
        self.save_dir = save_dir

    def on_epoch_end(self, epoch, logs=None):
        if self.save_dir and (epoch + 1) % self.save_freq == 0:
            path = os.path.join(self.save_dir, str(epoch))
            self.model.save(path)

    def on_train_end(self, logs=None):
        if self.save_dir:
            self.model.save(os.path.join(self.save_dir, "final"))


class FaultTolerantCheckpoint(Callback):
    """Drive a ``checkpoint.CheckpointManager`` from the fit loop.

    Unlike :class:`ModelCheckpoint` (epoch-granular ``model.save``),
    this is the fault-tolerance path: per-STEP policy checks, async
    atomic saves, and an end-of-training drain so the last commit
    lands. The manager is bound to the fitted network/optimizer at
    train begin if it was constructed bare. Saves key off the global
    optimizer step so resume semantics match the compiled trainer's.
    """

    def __init__(self, manager):
        super().__init__()
        self.manager = manager
        self._it = 0

    def on_train_begin(self, logs=None):
        self.manager.bind(
            self.model.network, getattr(self.model, "_optimizer", None)
        )

    def _global_step(self):
        opt = getattr(self.model, "_optimizer", None)
        n = getattr(opt, "_step_count", 0) if opt is not None else 0
        return n or self._it

    def on_train_batch_end(self, step, logs=None):
        # no logs read here: the sync-free fit path stays sync-free
        # (the manager snapshots device refs, it never fetches)
        self._it += 1
        self.manager.on_step(self._global_step())

    def on_train_end(self, logs=None):
        self.manager.finalize()


class ResilientTraining(Callback):
    """Attach the resilient-training runtime to a fitted model.

    Wires a ``training.AnomalySentinel`` (and optionally a
    ``training.TrainWatchdog``) into the model's compiled train step
    as soon as it exists — ``Model.fit(sentinel=...)`` is sugar for
    appending this callback. The sentinel's skip/abort rungs work
    as in the raw trainer; ROLLBACK inside ``fit`` is
    rollback-without-replay: a DataLoader cannot rewind, so the fit
    loop restores the last committed checkpoint and continues with the
    NEXT batch (the batches between commit and anomaly are lost, the
    run is not). For bit-identical replay semantics drive the trainer
    with ``training.run_resilient`` instead.

    Works only on the jit fast path (``prepare(jit_compile=True)``):
    the eager path applies its optimizer update before any loss value
    exists to judge, so there is nothing for the ladder to undo there.
    """

    def __init__(self, sentinel, watchdog=None):
        super().__init__()
        self.sentinel = sentinel
        self.watchdog = watchdog

    def on_train_begin(self, logs=None):
        # the compiled step is built lazily on the first fit step; the
        # model attaches these the moment it constructs the trainer
        self.model._sentinel = self.sentinel
        self.model._watchdog = self.watchdog
        jit_step = getattr(self.model, "_jit_step", None)
        if jit_step is not None:
            jit_step.attach_sentinel(self.sentinel)
            if self.watchdog is not None:
                self.watchdog.attach(jit_step)
        if self.watchdog is not None:
            self.watchdog.start()

    def on_train_end(self, logs=None):
        if self.watchdog is not None:
            self.watchdog.stop()


class EarlyStopping(Callback):
    def __init__(self, monitor="loss", mode="auto", patience=0, verbose=1,
                 min_delta=0, baseline=None, save_best_model=True):
        super().__init__()
        self.monitor = monitor
        self.patience = patience
        self.verbose = verbose
        self.min_delta = abs(min_delta)
        self.baseline = baseline
        self.save_best_model = save_best_model
        if mode == "auto":
            mode = "min" if "loss" in monitor else "max"
        self.mode = mode
        self.best = None
        self.wait = 0
        self.stopped_epoch = 0

    def _better(self, cur, best):
        if self.mode == "min":
            return cur < best - self.min_delta
        return cur > best + self.min_delta

    def on_eval_end(self, logs=None):
        logs = logs or {}
        cur = logs.get(self.monitor)
        if cur is None:
            return
        if isinstance(cur, (list, tuple)):
            cur = cur[0]
        if self.best is None or self._better(cur, self.best):
            self.best = cur
            self.wait = 0
            if self.save_best_model and self.params.get("save_dir"):
                self.model.save(os.path.join(self.params["save_dir"], "best_model"))
        else:
            self.wait += 1
            if self.wait >= self.patience:
                self.model.stop_training = True
                if self.verbose:
                    print(f"Early stopping: best {self.monitor}={self.best}")


class LRScheduler(Callback):
    """Steps the optimizer's LRScheduler per batch or per epoch."""

    def __init__(self, by_step=True, by_epoch=False):
        super().__init__()
        assert by_step != by_epoch
        self.by_step = by_step

    def _sched(self):
        from ..optimizer.lr import LRScheduler as Sched

        opt = getattr(self.model, "_optimizer", None)
        if opt is not None and isinstance(opt._lr, Sched):
            return opt._lr
        return None

    def on_train_batch_end(self, step, logs=None):
        s = self._sched()
        if self.by_step and s is not None:
            s.step()

    def on_epoch_end(self, epoch, logs=None):
        s = self._sched()
        if not self.by_step and s is not None:
            s.step()


class VisualDL(Callback):
    """Stub: visualdl is not available in this image; scalars are appended
    to a plain log file so training curves remain inspectable.

    ``log_freq``: write (and therefore READ the logs) every N steps.
    Reading per-step logs materializes the sync-free fit path's lazy
    values — a host<->device round trip — so per-step scalars cost
    throughput; raise log_freq to amortize.
    """

    def __init__(self, log_dir="./log", log_freq=1):
        super().__init__()
        self.log_dir = log_dir
        self.log_freq = int(log_freq)
        self._step = 0

    def on_train_batch_end(self, step, logs=None):
        self._step += 1
        if self._step % self.log_freq != 0:
            return  # no logs read off-cadence: lazy values stay lazy
        os.makedirs(self.log_dir, exist_ok=True)
        with open(os.path.join(self.log_dir, "scalars.txt"), "a") as f:
            for k, v in (logs or {}).items():
                if isinstance(v, numbers.Number):
                    f.write(f"{self._step}\t{k}\t{v}\n")


def config_callbacks(callbacks=None, model=None, batch_size=None, epochs=None,
                     steps=None, log_freq=1, verbose=2, save_freq=1,
                     save_dir=None, metrics=None, mode="train"):
    cbks = list(callbacks or [])
    if not any(isinstance(c, ProgBarLogger) for c in cbks) and verbose:
        cbks = [ProgBarLogger(log_freq, verbose=verbose)] + cbks
    if not any(isinstance(c, ModelCheckpoint) for c in cbks):
        cbks = cbks + [ModelCheckpoint(save_freq, save_dir)]
    if not any(isinstance(c, LRScheduler) for c in cbks):
        cbks = cbks + [LRScheduler()]
    lst = CallbackList(cbks)
    lst.set_model(model)
    lst.set_params({
        "batch_size": batch_size,
        "epochs": epochs,
        "steps": steps,
        "verbose": verbose,
        "metrics": metrics or [],
        "save_dir": save_dir,
    })
    return lst


class ReduceLROnPlateau(Callback):
    """Scale the LR down when a monitored metric stops improving
    (reference: python/paddle/hapi/callbacks.py ReduceLROnPlateau)."""

    def __init__(self, monitor="loss", factor=0.1, patience=10,
                 verbose=1, mode="auto", min_delta=1e-4, cooldown=0,
                 min_lr=0.0):
        super().__init__()
        self.monitor = monitor
        self.factor = float(factor)
        self.patience = int(patience)
        self.verbose = verbose
        self.min_delta = abs(float(min_delta))
        self.cooldown = int(cooldown)
        self.min_lr = float(min_lr)
        if mode == "max" or (mode == "auto" and "acc" in monitor):
            self._better = lambda cur, best: cur > best + self.min_delta
            self._best = -float("inf")
        else:
            self._better = lambda cur, best: cur < best - self.min_delta
            self._best = float("inf")
        self._wait = 0
        self._cooldown_left = 0

    def _current(self, logs):
        v = (logs or {}).get(self.monitor)
        if isinstance(v, (list, tuple)):
            v = v[0]
        return None if v is None else float(v)

    def _step(self, logs):
        cur = self._current(logs)
        if cur is None:
            return
        if self._cooldown_left > 0:
            # cooldown suppresses patience counting entirely
            self._cooldown_left -= 1
            self._wait = 0
            if self._better(cur, self._best):
                self._best = cur
            return
        if self._better(cur, self._best):
            self._best = cur
            self._wait = 0
            return
        self._wait += 1
        if self._wait >= self.patience:
            opt = getattr(self.model, "_optimizer", None)
            if opt is None:
                return
            lr_obj = opt._lr
            if hasattr(lr_obj, "last_lr"):  # LRScheduler
                new = max(float(lr_obj.last_lr) * self.factor, self.min_lr)
                lr_obj.last_lr = new
                if hasattr(lr_obj, "base_lr"):
                    lr_obj.base_lr = new
            else:
                new = max(float(lr_obj) * self.factor, self.min_lr)
                opt._lr = new
            if self.verbose:
                print(f"ReduceLROnPlateau: lr -> {new:.3e}")
            self._wait = 0
            self._cooldown_left = self.cooldown

    # At most ONE patience step per epoch. fit() fires on_epoch_end
    # (train logs) and then, with eval_data, on_eval_end (eval logs);
    # eval is the authoritative signal, so epoch-end stashes its logs
    # and eval-end either overrides or the stash flushes at the next
    # epoch boundary / train end.
    def on_epoch_end(self, epoch, logs=None):
        self._flush()  # previous epoch's stash, if eval never consumed it
        self._pending = dict(logs or {})

    def on_eval_end(self, logs=None):
        self._pending = dict(logs or {})
        self._flush()

    def on_train_end(self, logs=None):
        self._flush()

    def _flush(self):
        pending = getattr(self, "_pending", None)
        if pending is not None:
            self._pending = None
            self._step(pending)
