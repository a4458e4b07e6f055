"""Job ``serve_latent_moe``: job ``serve`` as it stands, for a
latent-attention expert decoder, whose decode step costs what the
routing made it cost. After ``serve.run`` it puts this model's own
counts into ``obs["work"]`` (``latent_moe_counts``), fed by the two
histograms the engine keeps for it, ``experts_touched`` and
``resident_tokens``, between the window's two reports:

- ``latent_moe_decode_bytes_per_step``: the bytes a decode step had to
  read (``decode_step_roofline.latent_moe``);
- ``moe_experts_bytes_per_step``: the touched experts' bytes alone
  (``moe_experts_roofline.serve``);
- ``latent_moe_decode_flops_per_step``, for the line only.

The dense count ``decode_bytes_per_step`` that ``serve`` leaves there
(K and V per head, every weight once) is not this model's and is taken
out. A program without the two histograms (the parent) leaves the work
empty, and the metrics that read it are left out of the line.

It also holds the net to the reference more sharply than 128 served
tokens can (``check_path``; the reference's docstring has the limits
and why the served tokens alone cannot tell a lower precision from
this model's own routing noise). After the run, on the net it built
and ``serve`` served, for ONE seeded sequence as long as the cell's
prefill bucket:

- the SERVED PATH's logits, teacher-forced (``builder.
  served_path_logits``: bucketed prefill, adopt into pages, paged
  absorbed decode at the engine's sizes), against the reference's at
  the positions where the reference's own routing is most decided
  (``reference.decisive_rows``: nothing of the served path's choices
  is handed over);
- the program's expert FFN (router, dispatch, grouped matmuls, shared
  expert) on the reference's own FFN inputs, against the reference's
  on the same inputs: the outputs, and the chosen experts.

``correct`` is the served tokens' verdict AND these.
"""
from __future__ import annotations

import gc
import types

import numpy as np

from benchmarks import harness, latent_moe_counts as counts
from benchmarks.jobs import serve

def window_mean(pair, name):
    """Mean of one engine histogram between the window's two reports;
    None where it took no sample (or does not exist)."""
    h0, h1 = (r.get(name) or {} for r in pair)
    n = h1.get("count", 0) - h0.get("count", 0)
    return (h1.get("sum", 0.0) - h0.get("sum", 0.0)) / n if n > 0 else None


def step_work(cfg, batch, touched, resident):
    """``obs["work"]`` entries of one mean decode step."""
    if touched is None or resident is None:
        return {}
    return {
        "latent_moe_decode_bytes_per_step":
            counts.decode_bytes_per_step(cfg, touched, resident),
        "moe_experts_bytes_per_step": touched * counts.expert_bytes(cfg),
        "latent_moe_decode_flops_per_step":
            counts.decode_flops_per_step(cfg, batch, resident),
    }


def reference_side(ctx, weights):
    """What the comparisons need of the float32 reference, from
    ``weights`` (the net's, as the seed made them): one forward of one
    seeded sequence at the served check's padded length (no second
    shape compiles), its logits at the decisive positions, and the
    expert FFN of every expert layer on its own input, that input
    rounded to the type the net is served in."""
    import jax.numpy as jnp

    cfg, ref, cell = ctx.config, ctx.reference, ctx.cell
    spec = cell["path_check"]
    tokens, rows = int(spec["tokens"]), int(cell["engine"]["max_batch_size"])
    ids = np.random.default_rng(ctx.seed + 2).integers(
        0, cfg["vocab_size"], tokens)
    padded = np.zeros((max(tokens, int(cell["check"]["pad_to"])),), np.int32)
    padded[:tokens] = ids
    routing = []
    h = ref.hidden(weights, cfg, jnp.asarray(padded), routing)
    margins = ref.least_margins(routing)
    decode_rows = ref.decisive_rows(margins, int(spec["from"]), tokens,
                                    int(spec["steps"]) * rows)
    prefill_rows = ref.decisive_rows(
        margins, min(cell["check"]["prompt_lens"]) - 1, tokens,
        int(spec["prefill_rows"]))
    at = np.concatenate([decode_rows, prefill_rows])
    want = np.asarray(ref.head(
        h[jnp.asarray(at)], weights["model.norm.weight"],
        weights["lm_head.weight"], eps=float(cfg["rms_norm_eps"])))
    served = weights["model.norm.weight"].dtype
    ffn_rows = np.linspace(0, tokens - 1, int(spec["ffn_rows"])).astype(
        np.int32)
    ffn = {}
    for index, (_, _, ffn_in) in zip(ref.expert_layers(cfg), routing):
        h_in = ffn_in[jnp.asarray(ffn_rows)].astype(served)
        prefix = f"model.layers.{index}.mlp."
        y, chosen, margin = ref.expert_ffn(
            h_in, {k[len(prefix):]: v for k, v in weights.items()
                   if k.startswith(prefix)}, moe=ref.moe_static(cfg))
        ffn[index] = (h_in, np.asarray(y), np.asarray(chosen),
                      np.asarray(margin))
    return {"ids": ids, "decode_rows": decode_rows,
            "prefill_rows": prefill_rows, "want": want, "ffn": ffn,
            "chosen": [np.asarray(r[0]) for r in routing],
            "least_margin": float(margins[decode_rows].min()),
            "least_margin_median": float(np.median(margins[:tokens]))}


def served_side(ctx, net, side):
    """The program's numbers beside ``side``'s: the relative error a
    position of the served path's decode logits and of its prefill
    logits, and of the expert FFN's outputs a token, with the share of
    decided tokens that went to another set of experts."""
    ref, builder, engine = ctx.reference, ctx.builder, ctx.cell["engine"]
    n = len(side["decode_rows"])
    first, decoded = builder.served_path_logits(
        net, engine, side["ids"], [int(r) + 1 for r in side["prefill_rows"]],
        side["decode_rows"])
    prefill_err = ref.relative_logit_errors(first, side["want"][n:])
    decode_err = ref.relative_logit_errors(
        decoded.reshape(n, -1), side["want"][:n])
    got = builder.expert_layer_outputs(
        net, {index: v[0] for index, v in side["ffn"].items()})
    ffn_err, decided, elsewhere = [], 0, 0
    for index, (_, y, chosen, margin) in side["ffn"].items():
        keep = margin >= ref.ROUTE_DECIDED
        ffn_err.append(ref.relative_errors(got[index][0], y)[keep])
        decided += int(keep.sum())
        elsewhere += int((np.sort(got[index][1], -1)
                          != np.sort(chosen, -1)).any(-1)[keep].sum())
    return {"decode_err": decode_err, "prefill_err": prefill_err,
            "ffn_err": np.concatenate(ffn_err),
            "route_elsewhere": elsewhere / max(decided, 1),
            "route_decided": decided}


def judge(ctx, side, got):
    """The four readings beside their limits, on one line; True where
    all hold."""
    ref = ctx.reference
    d, p, f = got["decode_err"], got["prefill_err"], got["ffn_err"]
    readings = {
        "path_err_p90": (float(np.percentile(d, 90)), ref.PATH_ERR),
        "prefill_err_median": (float(np.median(p)), ref.PATH_ERR),
        "ffn_err_p90": (float(np.percentile(f, 90)), ref.FFN_ERR),
        "route_elsewhere": (got["route_elsewhere"], ref.ROUTE_ELSEWHERE),
    }
    ok = all(np.isfinite(v) and v <= limit for v, limit in readings.values())
    harness.line(
        "check_path", positions=len(d), least_margin=side["least_margin"],
        least_margin_median_all=side["least_margin_median"],
        **{k: v for k, (v, _) in readings.items()},
        **{f"allowed_{k}": limit for k, (_, limit) in readings.items()},
        path_err_mean=float(d.mean()), path_err_median=float(np.median(d)),
        path_err_p10=float(np.percentile(d, 10)), path_err_max=float(d.max()),
        prefill_err_max=float(p.max()), ffn_err_median=float(np.median(f)),
        ffn_err_max=float(f.max()), route_decided=got["route_decided"],
        ok=bool(ok))
    return bool(ok)


def check_path(ctx, net):
    side = reference_side(ctx, ctx.builder.weights(net))
    return judge(ctx, side, served_side(ctx, net, side))


def run(ctx):
    cell = ctx.cell
    # the job builds the net, so that it still holds it after the run;
    # job serve is handed the same one
    built = ctx.builder.build(ctx.config, ctx.seed,
                              cell.get("param_dtype", "bfloat16"))
    inner = types.SimpleNamespace(**vars(ctx))
    inner.builder = types.SimpleNamespace(
        build=lambda *_: built, weights=ctx.builder.weights)
    res = serve.run(inner)
    gc.collect()                   # the closed engine's arena
    harness.note("serve_latent_moe: the served path's logits and the "
                 "expert layers against the reference's")
    res["correct"] = bool(check_path(ctx, built[0]) and res["correct"])
    obs, cfg = res["obs"], ctx.config
    touched = window_mean(obs["engine_report"], "experts_touched")
    resident = window_mean(obs["engine_report"], "resident_tokens")
    obs["work"].pop("decode_bytes_per_step", None)
    obs["work"].update(step_work(
        cfg, int(obs["engine"]["max_batch_size"]), touched, resident))
    # what experts_touched.serve is a share of
    obs["engine"] = dict(
        obs["engine"], routed_expert_slots=counts.expert_layers(cfg)
        * cfg["n_routed_experts"])
    harness.line("latent_moe_work", experts_touched_mean=touched,
                 resident_tokens_mean=resident,
                 expert_bytes=counts.expert_bytes(cfg),
                 latent_bytes_per_token=counts.latent_bytes_per_token(cfg),
                 **obs["work"])
    return res
