"""Benchmark entry: prints ONE JSON line with the headline metric.

Flagship bench: whole-step compiled training throughput of a Llama-shaped
decoder (RMSNorm + rope + causal attention + SwiGLU — BASELINE config #4's
model family) at the largest single-chip-fitting size with fp32 Adam:
748M params (hidden 2048, 12 layers, intermediate 5632), bf16 compute
(AMP O2). ``vs_baseline`` is measured-MFU / 0.40 (a 40%-MFU A100 Fleet
assumption — no published reference numbers exist; BASELINE.md records
the provenance gap). FLOPs use the standard 6N + attention accounting
(models/llama.py:flops_per_token).

``--all`` additionally times every BASELINE acceptance config (LeNet fit,
ResNet-50, BERT-base, the round-3 Llama-330M, GPT-MoE) and prints a
per-config table — the regression net for perf anywhere in the stack
(results recorded in BENCH_NOTES.md). ``--profile`` writes a jax
profiler trace to ./bench_trace.

Sizing notes (measured on v5e 16G, see BENCH_NOTES.md): B=4 is the
flagship sweet spot (B=8 OOMs by 250M; B=6 and S=2048 variants measured
slower); 14 layers fits but scores lower MFU than 12.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _timed_steps(step, inputs, labels, iters, warmup=3, profile=False):
    """Shared methodology for every config: warmup (incl. compile) +
    device sync, then the timed steady-state loop + sync. Callers that
    want contention-robust numbers use :func:`_timed_windows` directly
    (the flagship does)."""
    return sum(_timed_windows(step, inputs, labels, iters,
                              warmup=warmup, profile=profile))


def _timed_windows(step, inputs, labels, iters, warmup=3, profile=False,
                   windows=1):
    """Per-window wall times (seconds). Multiple windows make a single
    contended capture diagnosable: a transient slowdown shows up as one
    outlier window instead of silently poisoning the only number
    (it happened to a round-4 driver capture)."""
    import numpy as np

    for _ in range(warmup):
        loss, _ = step(inputs, labels)
    float(np.asarray(loss.numpy()))
    if profile:
        import jax

        jax.profiler.start_trace("bench_trace")
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, _ = step(inputs, labels)
        float(np.asarray(loss.numpy()))
        times.append(time.perf_counter() - t0)
    if profile:
        import jax

        jax.profiler.stop_trace()
    return times


def _llama_step_bench(cfg, B, S, iters, amp="O2", profile=False,
                      windows=1):
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.trainer import CompiledTrainStep
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(0)
    net = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=net.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), labels.reshape([-1])
        )

    step = CompiledTrainStep(
        net, loss_fn, opt, amp_level=amp, amp_dtype="bfloat16"
    )
    rng = np.random.RandomState(0)
    ids = [Tensor(jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))))]
    labels = [Tensor(jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))))]
    times = _timed_windows(step, ids, labels, iters, profile=profile,
                           windows=windows)
    med = sorted(times)[len(times) // 2]
    tok = B * S * iters / med
    flops = net.flops_per_token(S) * B * S * iters / med
    return tok, flops, {
        "n_params": net.num_params(),
        "window_sec": [round(t, 4) for t in times],
        "per_step_ms": round(1e3 * med / iters, 3),
    }


def _on_tpu():
    import jax

    return any(d.platform != "cpu" for d in jax.devices())


def _require_backend():
    """A measurement that finds no chip fails. The CPU smoke (tiny
    model, ``*_cpu_smoke`` metric names) runs only in a process that was
    put on the CPU on purpose — never because no chip was found."""
    from paddle_tpu.core.device import explicitly_on_cpu

    if not _on_tpu() and not explicitly_on_cpu():
        raise SystemExit(
            "bench.py: jax found no accelerator. A benchmark without a "
            "chip is not a benchmark; set JAX_PLATFORMS=cpu to run the "
            "CPU smoke on purpose."
        )


def _peak():
    """bf16 peak FLOP/s of this process's device kind, from the one
    table there is (observability/step_meter.py). A device that is not
    in the table is an error, not a default."""
    import jax

    from paddle_tpu.observability.step_meter import peak_flops_per_device

    peak = peak_flops_per_device()
    if peak is None:
        raise SystemExit(
            f"bench.py: no peak FLOP/s known for device kind "
            f"{jax.devices()[0].device_kind!r}; add it to "
            "PEAK_FLOPS_BY_KIND with its source"
        )
    return peak


def _device_desc():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform,
            "device": getattr(d, "device_kind", str(d)),
            "n_devices": len(jax.devices())}


def flagship(profile=False):
    """Flagship metric. Self-describing by design (round-4 lesson: a
    contended driver capture recorded 8,099 tok/s for a 26k tok/s
    program, and the JSON carried nothing to diagnose it): the output
    echoes platform + device kind, the full model/batch config, the
    per-step ms, and all three timed-window wall times — median-of-3 is
    the reported number, so one contended window cannot poison the
    result, and an anomalous capture is visible in ``window_sec``
    skew. In a process put on the CPU on purpose (``_require_backend``)
    the flagship metric NAME is refused — a ``*_cpu_smoke`` metric is
    emitted instead so the tiny smoke model can never masquerade as the
    750M number."""
    from paddle_tpu.models import LlamaConfig

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            max_position_embeddings=1024,
        )
        B, S, iters, windows = 4, 1024, 10, 3
    else:
        cfg = LlamaConfig.tiny()
        B, S, iters, windows = 2, 64, 3, 3

    tok, flops, detail = _llama_step_bench(
        cfg, B, S, iters, amp="O2" if on_tpu else None, profile=profile,
        windows=windows,
    )
    mfu = flops / _peak() if on_tpu else None
    metric = ("train_tokens_per_sec_per_chip_llama750m" if on_tpu
              else "train_tokens_per_sec_cpu_smoke")
    from paddle_tpu.parallel import layout as layout_mod

    out = {
        "metric": metric,
        "value": round(tok, 1),
        "unit": "tokens/s",
        "layout_policy": layout_mod.get_policy().name,
        "vs_baseline": round(mfu / 0.40, 4) if on_tpu else None,
        # the denominator is an ASSUMPTION, not a published number
        # (BASELINE.md provenance): vs_baseline = measured_MFU / 0.40,
        # the 40%-MFU A100 Fleet-parity bar
        "baseline_note": f"measured_mfu={round(mfu, 4)} vs assumed "
                         "0.40-MFU A100 Fleet parity (no published "
                         "reference numbers exist)" if on_tpu else
                         "CPU smoke run (JAX_PLATFORMS=cpu); NOT the "
                         "flagship number (run on a TPU chip for that)",
        "config": {"model": "llama-decoder",
                   "n_params": detail["n_params"],
                   "hidden": cfg.hidden_size,
                   "layers": cfg.num_hidden_layers,
                   "B": B, "S": S, "amp": "O2-bf16" if on_tpu else None,
                   "iters_per_window": iters, "windows": windows},
        "per_step_ms": detail["per_step_ms"],
        "window_sec": detail["window_sec"],
    }
    out.update(_device_desc())
    return out


# ------------------------------------------------------- BASELINE configs
def bench_llama330m():
    """Round-3 flagship, kept for history continuity."""
    from paddle_tpu.models import LlamaConfig

    on = _on_tpu()
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=4096,
        num_hidden_layers=16, num_attention_heads=16,
        max_position_embeddings=1024,
    ) if on else LlamaConfig.tiny()
    tok, flops, _ = _llama_step_bench(
        cfg, 8 if on else 2, 1024 if on else 64, 20 if on else 2,
        amp="O2" if on else None,
    )
    return {"config": "llama-330m step", "value": round(tok, 1),
            "unit": "tokens/s", "mfu": round(flops / _peak(), 4) if on else None}


def bench_lenet_fit():
    """BASELINE config #1: LeNet/MNIST via paddle.Model.fit (hapi)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.vision.models import LeNet

    on = _on_tpu()
    n, bs, epochs = (4096, 256, 2) if on else (128, 64, 1)
    rng = np.random.RandomState(0)
    xs = rng.randn(n, 1, 28, 28).astype(np.float32)
    ys = rng.randint(0, 10, (n, 1)).astype(np.int64)

    class DS:
        def __len__(self):
            return n

        def __getitem__(self, i):
            return xs[i], ys[i]

    paddle.seed(0)
    model = paddle.Model(LeNet())
    model.prepare(
        paddle.optimizer.Adam(1e-3, parameters=model.network.parameters()),
        paddle.nn.CrossEntropyLoss(),
    )
    # epoch 1 includes compile; time epoch 2 (steady state)
    model.fit(DS(), batch_size=bs, epochs=1, verbose=0)
    t0 = time.perf_counter()
    model.fit(DS(), batch_size=bs, epochs=epochs - 1 or 1, verbose=0)
    dt = (time.perf_counter() - t0) / max(epochs - 1, 1)
    return {"config": "lenet Model.fit epoch", "value": round(n / dt, 1),
            "unit": "images/s", "mfu": None}


def bench_resnet50():
    """BASELINE config #2's model: ResNet-50 train step (single chip;
    the DP axis is exercised by tests/dryrun — one-chip throughput is
    the per-chip term of the DP number)."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.trainer import CompiledTrainStep
    from paddle_tpu.vision.models import resnet50

    on = _on_tpu()
    B, iters = (64, 10) if on else (2, 2)
    paddle.seed(0)
    net = resnet50()
    opt = paddle.optimizer.Momentum(
        0.1, momentum=0.9, parameters=net.parameters()
    )

    def loss_fn(logits, labels):
        import paddle_tpu.nn.functional as F

        return F.cross_entropy(logits, labels)

    step = CompiledTrainStep(
        net, loss_fn, opt, amp_level="O2" if on else None,
        amp_dtype="bfloat16",
    )
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, 3, 224 if on else 32, 224 if on else 32),
                    jnp.float32)
    y = jnp.asarray(rng.randint(0, 1000, (B,)))
    dt = _timed_steps(step, [Tensor(x)], [Tensor(y)], iters)
    return {"config": "resnet50 step", "value": round(B * iters / dt, 1),
            "unit": "images/s", "mfu": None}


def bench_bert_base():
    """BASELINE config #3: BERT-base pretraining step."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.trainer import CompiledTrainStep
    from paddle_tpu.models import (
        BertConfig,
        BertForPretraining,
        BertPretrainingCriterion,
    )

    on = _on_tpu()
    cfg = BertConfig.bert_base() if on else BertConfig.tiny()
    B, S, iters = (16, 512, 10) if on else (2, 32, 2)
    paddle.seed(0)
    net = BertForPretraining(cfg)
    crit = BertPretrainingCriterion(cfg.vocab_size)
    opt = paddle.optimizer.AdamW(1e-4, parameters=net.parameters())

    def loss_fn(pred_scores, seq_rel, mlm_labels, nsp_labels):
        return crit(pred_scores, seq_rel, mlm_labels, nsp_labels)

    step = CompiledTrainStep(
        net, loss_fn, opt, amp_level="O2" if on else None,
        amp_dtype="bfloat16",
    )
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    mlm = jnp.asarray(
        np.where(rng.rand(B, S) < 0.15,
                 rng.randint(0, cfg.vocab_size, (B, S)), -1)
    )
    nsp = jnp.asarray(rng.randint(0, 2, (B,)))
    dt = _timed_steps(step, [Tensor(ids)], [Tensor(mlm), Tensor(nsp)],
                      iters)
    return {"config": "bert-base step", "value": round(B * S * iters / dt, 1),
            "unit": "tokens/s", "mfu": None}


def bench_gpt_moe():
    """BASELINE config #5: GPT-MoE train step (gshard gate, 8 experts)."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.trainer import CompiledTrainStep
    from paddle_tpu.models import GPTMoEConfig, GPTMoEForCausalLM

    on = _on_tpu()
    cfg = GPTMoEConfig() if on else GPTMoEConfig.tiny()
    B, S, iters = (8, 1024, 10) if on else (2, 32, 2)
    paddle.seed(0)
    net = GPTMoEForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=net.parameters())

    def loss_fn(logits, labels):
        ce = F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), labels.reshape([-1])
        )
        return ce + cfg.aux_loss_weight * net.aux_loss()

    step = CompiledTrainStep(
        net, loss_fn, opt, amp_level="O2" if on else None,
        amp_dtype="bfloat16",
    )
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    dt = _timed_steps(step, [Tensor(ids)], [Tensor(labels)], iters)
    return {"config": "gpt-moe step", "value": round(B * S * iters / dt, 1),
            "unit": "tokens/s", "mfu": None}


def run_all():
    """Every BASELINE acceptance config in turn; a config that raises
    ends the run."""
    rows = []
    for fn in (bench_lenet_fit, bench_resnet50, bench_bert_base,
               bench_llama330m, bench_gpt_moe):
        rows.append(fn())
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    return rows


def _long_context_impl(S=None, layout="long-context"):
    """Runs INSIDE a process whose backend already has the devices (the
    vmesh subprocess on CPU, the pod on TPU): hybrid llama train steps
    at long sequence length under the given layout policy, one
    self-describing JSON line on stdout.

    Geometry adapts to the device count: with >= 8 devices the full
    dp x pp2 x sep2 x mp2 hybrid runs (S=8192 on TPU — the long-context
    flagship); fewer devices run a dp x mp2 GSPMD hybrid (no pp ring /
    sep ring) so the record still measures the policy-routed loss
    path, labeled ``reduced``."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed.fleet.base.topology import (
        CommunicateTopology,
        HybridCommunicateGroup,
    )
    from paddle_tpu.jit.pipeline_trainer import CompiledPipelineTrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLMPipe
    from paddle_tpu.parallel import layout as layout_mod

    on_tpu = _on_tpu()
    n_dev = len(jax.devices())
    full = n_dev >= 8
    if full:
        geom = {"dp": n_dev // 8, "pp": 2, "sep": 2, "mp": 2}
    else:
        geom = {"dp": max(n_dev // 2, 1), "pp": 1, "sep": 1,
                "mp": 2 if n_dev >= 2 else 1}
    hcg = HybridCommunicateGroup(CommunicateTopology(
        ["dp", "pp", "sharding", "sep", "mp"],
        [geom["dp"], geom["pp"], 1, geom["sep"], geom["mp"]],
    ))
    if on_tpu:
        # the flagship decoder at the long-context sequence length
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            max_position_embeddings=8192,
        )
        S = S or 8192
        B, iters, windows, amp = 4, 5, 3, "O2"
    else:
        cfg = LlamaConfig.tiny(
            vocab_size=64 * geom["mp"], hidden_size=32,
            intermediate_size=64, num_hidden_layers=4,
            num_attention_heads=4, max_position_embeddings=512,
        )
        S = S or 128
        B, iters, windows, amp = 4, 2, 3, None
    with layout_mod.use_policy(layout):
        paddle.seed(0)
        net = LlamaForCausalLMPipe(cfg, num_stages=geom["pp"])
        opt = paddle.optimizer.AdamW(
            1e-4, parameters=net.parameters()
        )
        step = CompiledPipelineTrainStep(
            net, lambda out, *lbls: net._loss_fn(out, *lbls), opt,
            micro_batches=2, amp_level=amp, amp_dtype="bfloat16",
        )
        rng = np.random.RandomState(0)
        ids = Tensor(jax.device_put(
            jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))),
            NamedSharding(hcg.mesh,
                          layout_mod.get_policy().batch_spec(2)),
        ))
        times = _timed_windows(step, [ids], [ids], iters,
                               windows=windows)
    med = sorted(times)[len(times) // 2]
    tok = B * S * iters / med
    flops = net.flops_per_token(S) * B * S * iters / med
    if on_tpu and full:
        metric = "train_tokens_per_sec_long_context_s8192"
    elif on_tpu:
        # a REAL chip measurement that could not run the pp/sep rings —
        # never label it cpu_smoke (consumers key CPU-vs-TPU off the
        # metric suffix)
        metric = "long_context_train_tokens_per_sec_reduced"
    else:
        metric = "long_context_train_tokens_per_sec_cpu_smoke"
    out = {
        "metric": metric,
        "value": round(tok, 1),
        "unit": "tokens/s",
        "layout_policy": layout_mod.resolve(layout).name,
        "mfu": round(flops / _peak(), 4) if on_tpu else None,
        "config": {"model": "llama-decoder-pipe",
                   "n_params": net.num_params(), "B": B, "S": S,
                   "amp": f"{amp}-bf16" if amp else None,
                   "iters_per_window": iters, "windows": windows},
        "geometry": geom,
        "per_step_ms": round(1e3 * med / iters, 3),
        "window_sec": [round(t, 4) for t in times],
    }
    if not full:
        out["reduced"] = (
            "< 8 devices: no room for the pp/sep rings — GSPMD-hybrid "
            "smoke of the long-context loss path, NOT the S=8192 "
            "flagship"
        )
    out.update(_device_desc())
    print(json.dumps(out))
    return out


def long_context():
    """``--long-context``: the S=8192 flagship config through the sep
    ring under the long-context layout policy. On a chipless box the
    measurement runs in a fresh 8-device virtual CPU mesh subprocess
    (backend init is process-global) and is labeled *_cpu_smoke."""
    if _on_tpu():
        return _long_context_impl()
    from tools.vmesh import run_in_virtual_cpu_mesh

    here = os.path.dirname(os.path.abspath(__file__))
    r = run_in_virtual_cpu_mesh(
        8, "import bench; bench._long_context_impl()", cwd=here,
        timeout=900,
    )
    sys.stderr.write(r.stderr)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise SystemExit(r.returncode)


def lower_7b_check():
    """``--lower-7b``: build + lower the Llama-2-7B Fleet hybrid train
    step (LazyGuard abstract params) on a virtual 8-device CPU mesh in a
    subprocess (backend init is process-global; see tools/vmesh.py)."""
    from tools.vmesh import run_in_virtual_cpu_mesh

    here = os.path.dirname(os.path.abspath(__file__))
    r = run_in_virtual_cpu_mesh(
        8, "from tools.lower_7b import lower_7b; lower_7b(write_notes=True)",
        cwd=here,
    )
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    if r.returncode != 0:
        raise SystemExit(r.returncode)


def tune_kernels():
    """``--tune``: measured-search flash attention's block configs at
    the flagship shapes (and the fp8 matmul's fp8-vs-bf16 verdict) and
    print ONE self-describing JSON
    record — chosen configs, per-candidate timings, and cache
    accounting (a repeat run on a tuned device reports 100% cache hits
    and zero re-measurements). Results persist in the tune cache
    (tools/kernel_tune_cache.json or PADDLE_TPU_TUNE_CACHE), which
    flash attention's selection reads at trace time."""
    from tools.kernel_tune import run_tune

    from paddle_tpu.parallel import layout as layout_mod

    rec = run_tune()
    # run_tune's device/platform are the NORMALIZED kind used in the
    # cache keys (e.g. "tpu-v5e", not "TPU v5 lite") — never clobber
    for k, v in _device_desc().items():
        rec.setdefault(k, v)
    rec.setdefault("layout_policy", layout_mod.get_policy().name)
    print(json.dumps(rec))
    return rec


def main(profile=False, all_configs=False):
    # flagship() names its metric *_cpu_smoke in a process that was put
    # on the CPU on purpose
    if all_configs:
        run_all()
    print(json.dumps(flagship(profile)))


if __name__ == "__main__":
    from paddle_tpu.jit import place_compile_cache

    if "--lower-7b" in sys.argv:
        # compiles for a virtual CPU mesh in a child; needs no chip
        lower_7b_check()
    else:
        _require_backend()
        place_compile_cache()
        if "--long-context" in sys.argv:
            long_context()
        elif "--tune" in sys.argv:
            tune_kernels()
        else:
            main(profile="--profile" in sys.argv,
                 all_configs="--all" in sys.argv)
