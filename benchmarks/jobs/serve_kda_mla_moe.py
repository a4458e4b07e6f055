"""Job ``serve_kda_mla_moe``: job ``serve`` as it stands, for a decoder
whose layers are KDA (a state a row) or NoPE-MLA (a latent a token)
with a leading dense layer and a held share of experts. As
``serve_linear_moe`` for its family (that job's docstring has the
reasons for each reading): it builds the net, refuses to go on unless
the program's parameter count equals ``kda_mla_moe_counts.
model_params``, hands the net to job ``serve``, and then

(1) puts into ``obs["work"]`` the least bytes and operations of a mean
decode step and of one prefill bucket (``kda_mla_moe_counts``), fed by
the engine's histograms ``experts_touched`` and ``resident_tokens``
between the window's two reports, under the keys the metric files
read:

- ``kda_mla_moe_decode_bytes_per_step``
  (``decode_step_roofline.kda_mla_moe``);
- ``latent_read_bytes_per_step``: ``resident_tokens`` x the stored
  latent's bytes (``latent_read_roofline.serve``);
- ``moe_experts_bytes_per_step``, ``kda_step_bytes_per_step``,
  ``kda_chunk_flops_per_prefill`` (the metrics the sibling cells
  report too);
- ``kda_mla_moe_decode_flops_per_step``, for the line only;

and takes the dense ``decode_bytes_per_step`` out;

(2) gives ``obs["engine"]`` ``routed_expert_slots`` (expert layers x
experts held) and ``assignment_slots`` (rows x experts per token x
expert layers), what ``experts_touched.serve``,
``local_assignments.serve`` and ``dispatch_rows.serve`` are shares of;

(3) runs ``check_path`` on the net that was served, after the window,
for ONE seeded sequence as long as the cell's prefill bucket: the
readings of ``serve_linear_moe``'s (the served path's teacher-forced
logits at its decode positions and its prefills' own rows; the row
state a whole prefill leaves and the state of the row whose steps end
at the sequence's end, every KDA layer and head; the two state kernels
alone on the reference's own float32 inputs; the expert FFN and the
chosen sets on the reference's own FFN inputs), and two of its own: the
MLA mixer's ABSORBED one-token step over pages a materialised prefill
wrote, on the reference's own mixer input, against the reference's K
and V per head made from the query and the latent as they are stored
(``builder.mla_step_outputs``); and what the ENGINE's own admission of
that sequence leaves a row (``builder.adopted_by_engine``: a
``PagedServingEngine`` built as the cell builds it, its own bucketed
prefill, page claim and adoption program, read back through the row's
page table as the program returns): the stored latent, every KDA
layer's state and its tail against the reference's. The served tokens
cannot see the latent at these lengths (random weights: a softmax over
16 k tokens averages its values nearly away), so this is what holds
the one mechanism the configuration forces, a ``[1, 16384, 640]`` block
scattered into pages beside a state and a tail copied into a row in one
adoption. ``correct`` is the served tokens' verdict AND these.
"""
from __future__ import annotations

import gc
import types

import numpy as np

from benchmarks import harness, kda_mla_moe_counts as counts
from benchmarks.jobs import serve, serve_linear_moe as linear
from benchmarks.jobs.serve_latent_moe import window_mean


def step_work(cfg, engine, bucket, touched, resident):
    """``obs["work"]`` entries of one mean decode step and of one
    prefill bucket."""
    rows = int(engine["max_batch_size"])
    work = {
        "kda_step_bytes_per_step": counts.kda_step_bytes(cfg, rows),
        "kda_chunk_flops_per_prefill": counts.kda_chunk_flops(cfg, bucket),
    }
    if touched is not None and resident is not None:
        work.update({
            "kda_mla_moe_decode_bytes_per_step":
                counts.decode_bytes_per_step(cfg, touched, resident, rows),
            "latent_read_bytes_per_step":
                resident * counts.latent_bytes_per_token(cfg),
            "moe_experts_bytes_per_step": touched * counts.expert_bytes(cfg),
            "kda_mla_moe_decode_flops_per_step":
                counts.decode_flops_per_step(cfg, rows, resident),
        })
    return work


def reference_side(ctx, weights):
    """What the comparisons need of the float32 reference, from
    ``weights`` (the net's, as the seed made them): one forward of one
    seeded sequence, its logits at the compared positions, every KDA
    layer's final state, the expert FFN of every expert layer on its
    own input and the MLA mixer on its own, those inputs rounded to the
    type the net is served in."""
    import jax.numpy as jnp

    cfg, ref, cell = ctx.config, ctx.reference, ctx.cell
    spec = cell["path_check"]
    tokens, rows = int(spec["tokens"]), int(cell["engine"]["max_batch_size"])
    steps = int(spec["steps"])
    ids = np.random.default_rng(ctx.seed + 2).integers(
        0, cfg["vocab_size"], tokens)
    lengths = linear.path_lengths(tokens, rows, steps, int(spec["stride"]))
    decode_at = (lengths[None, :] + np.arange(steps)[:, None]).reshape(-1)
    at = np.concatenate([decode_at, lengths - 1])
    routing, states = {}, {}
    h = ref.hidden(weights, cfg, jnp.asarray(ids), routing, states)
    want = np.asarray(ref.head(
        h[jnp.asarray(at)], weights["model.norm.weight"],
        weights["lm_head.weight"], eps=float(cfg["rms_norm_eps"])))
    del h
    served = weights["model.norm.weight"].dtype
    ffn_rows = np.linspace(0, tokens - 1, int(spec["ffn_rows"])).astype(
        np.int32)
    ffn = {}
    for index, (_, _, ffn_in) in routing.items():
        h_in = ffn_in[jnp.asarray(ffn_rows)].astype(served)
        routed, shared, chosen, margin = ref.expert_ffn(
            h_in, ref.layer_weights(weights, f"model.layers.{index}.mlp."),
            moe=ref.moe_static(cfg), share=ref.share_of(cfg))
        ffn[index] = (h_in, np.asarray(routed + shared), np.asarray(chosen),
                      np.asarray(margin))
    del routing
    static = ref.mixer_static(cfg)
    kda = [i for i, (state, _) in states.items() if state is not None]
    # the last KDA layer's recurrence alone, on its own float32 inputs
    fed, fed_state = ref.kda_inputs_and_state(
        states[kda[-1]][1],
        ref.layer_weights(weights, f"model.layers.{kda[-1]}.mixer."),
        heads=static["kda_heads"], dim=static["kda_dim"], neg_eigval=False)
    # the MLA layer's mixer alone, on its own input as it is served
    mla = next(i for i, (state, _) in states.items() if state is None)
    mla_in = states[mla][1].astype(served)
    mla_rows = lengths + steps - 1
    rounded = (str(served), str(cell["engine"]["cache_dtype"]))
    # ... on the query and the latent as a deployment in these types
    # holds them: the reading is the absorbed contraction's own error
    mla_out = np.asarray(ref.mla_mixer(
        mla_in, ref.layer_weights(weights, f"model.layers.{mla}.mixer."),
        rounded=rounded,
        **{k: static[k] for k in ("heads", "dn", "dr", "dv", "eps")})
        [jnp.asarray(mla_rows)])
    # what the engine's own admission of the whole sequence must leave
    # a row beside the final states: the MLA layer's latent as it is
    # stored, and every KDA layer's tail
    latent = np.asarray(ref.mla_latent(
        mla_in, ref.layer_weights(weights, f"model.layers.{mla}.mixer."),
        eps=static["eps"], rounded=rounded))
    tails = {i: np.asarray(ref.kda_tail(
        states[i][1], ref.layer_weights(weights, f"model.layers.{i}.mixer.")))
        for i in kda}
    return {"ids": ids, "lengths": lengths, "steps": steps, "want": want,
            "fed": tuple(np.asarray(a) for a in fed),
            "fed_state": np.asarray(fed_state),
            "states": {i: np.asarray(states[i][0]) for i in kda},
            "ffn": ffn, "mla_in": mla_in, "mla_rows": mla_rows,
            "mla_out": mla_out, "mla_layer": mla, "latent": latent,
            "tails": tails}


def served_side(ctx, net, side):
    """The program's numbers beside ``side``'s: the sibling job's (the
    served path's logits, the states, the state kernels, the expert
    FFN and the chosen sets: its functions read nothing of a family),
    the absorbed MLA step's relative error a row and what the engine's
    own admission leaves (``adopted_side``)."""
    got = linear.served_side(ctx, net, side)
    ref, engine = ctx.reference, ctx.cell["engine"]
    got["mla_step_err"] = ref.relative_errors(
        ctx.builder.mla_step_outputs(net, engine, side["mla_in"],
                                     side["mla_rows"]), side["mla_out"])
    got.update(adopted_side(ctx, net, side))
    return got


def adopted_side(ctx, net, side, plant=None):
    """What the ENGINE's own admission of the whole sequence leaves the
    row (``builder.adopted_by_engine``: its prefill, page claim and
    adoption program, read back through the row's page table) beside
    the reference's: the stored latent's relative error a token, the
    state's a head and the tail's a position, every KDA layer."""
    ref = ctx.reference
    left = ctx.builder.adopted_by_engine(net, ctx.cell["engine"],
                                         side["ids"], plant)
    kda = sorted(side["states"])
    width = side["latent"].shape[-1]
    return {
        "adopted_latent_err": ref.relative_errors(
            left[side["mla_layer"]][0][:, :width], side["latent"]),
        "adopted_state_err": np.concatenate([
            ref.state_errors(left[i][0], side["states"][i]) for i in kda]),
        "adopted_tail_err": np.concatenate([
            ref.relative_errors(left[i][1], side["tails"][i]) for i in kda]),
    }


def judge(ctx, got):
    """The thirteen readings beside their limits, on one line; True
    where all hold."""
    ref = ctx.reference
    al, as_, at = (got[k] for k in (
        "adopted_latent_err", "adopted_state_err", "adopted_tail_err"))
    d, p, s, t, k, f, m = (got[k] for k in (
        "decode_err", "prefill_err", "state_err", "stepped_state_err",
        "kernel_state_err", "ffn_err", "mla_step_err"))
    readings = {
        "path_err_median": (float(np.median(d)), ref.PATH_ERR),
        "path_err_p90": (float(np.percentile(d, 90)), ref.PATH_ERR_P90),
        "prefill_err_median": (float(np.median(p)), ref.PATH_ERR),
        "state_err_median": (float(np.median(s)), ref.PATH_STATE_ERR),
        "stepped_state_err_median": (float(np.median(t)),
                                     ref.PATH_STATE_ERR),
        "kernel_state_err_max": (float(k.max()), ref.KERNEL_STATE_ERR),
        "mla_step_err_p90": (float(np.percentile(m, 90)), ref.MLA_STEP_ERR),
        "ffn_err_p90": (float(np.percentile(f, 90)), ref.FFN_ERR),
        "route_elsewhere": (got["route_elsewhere"], ref.ROUTE_ELSEWHERE),
        "adopted_latent_err_median": (float(np.median(al)), ref.ADOPTED_ERR),
        "adopted_latent_err_p90": (float(np.percentile(al, 90)),
                                   ref.ADOPTED_LATENT_P90),
        "adopted_state_err_median": (float(np.median(as_)),
                                     ref.PATH_STATE_ERR),
        "adopted_tail_err_median": (float(np.median(at)), ref.ADOPTED_ERR),
    }
    ok = all(np.isfinite(v) and v <= limit for v, limit in readings.values())
    harness.line(
        "check_path", positions=len(d), prefills=len(p), heads=len(k),
        **{k: v for k, (v, _) in readings.items()},
        **{f"allowed_{k}": limit for k, (_, limit) in readings.items()},
        path_err_max=float(d.max()),
        prefill_err_max=float(p.max()),
        state_err_p90=float(np.percentile(s, 90)),
        stepped_state_err_p90=float(np.percentile(t, 90)),
        mla_step_err_median=float(np.median(m)),
        mla_step_err_max=float(m.max()),
        ffn_err_median=float(np.median(f)), ffn_err_max=float(f.max()),
        adopted_latent_err_max=float(al.max()),
        adopted_state_err_p90=float(np.percentile(as_, 90)),
        adopted_tail_err_max=float(at.max()),
        route_decided=got["route_decided"], ok=bool(ok))
    return bool(ok)


def check_path(ctx, net):
    side = reference_side(ctx, ctx.builder.weights(net))
    gc.collect()
    return judge(ctx, served_side(ctx, net, side))


def run(ctx):
    cell, cfg = ctx.cell, ctx.config
    # the job builds the net, so that it still holds it after the run;
    # job serve is handed the same one
    built = ctx.builder.build(cfg, ctx.seed,
                              cell.get("param_dtype", "bfloat16"))
    params = sum(int(p.size) for p in built[0].parameters())
    if params != counts.model_params(cfg):
        raise RuntimeError(
            f"the program holds {params} parameters, the counts file "
            f"reckons {counts.model_params(cfg)}")
    inner = types.SimpleNamespace(**vars(ctx))
    inner.builder = types.SimpleNamespace(
        build=lambda *_: built, weights=ctx.builder.weights)
    res = serve.run(inner)
    gc.collect()                   # the closed engine's arena and rows
    harness.note("serve_kda_mla_moe: the served path's logits, the row "
                 "state, the absorbed step and the expert layers against "
                 "the reference's")
    res["correct"] = bool(check_path(ctx, built[0]) and res["correct"])
    obs = res["obs"]
    touched = window_mean(obs["engine_report"], "experts_touched")
    resident = window_mean(obs["engine_report"], "resident_tokens")
    obs["work"].pop("decode_bytes_per_step", None)
    obs["work"].update(step_work(
        cfg, obs["engine"], int(cell["path_check"]["tokens"]), touched,
        resident))
    rows, layers = int(obs["engine"]["max_batch_size"]), \
        counts.expert_layers(cfg)
    obs["engine"] = dict(
        obs["engine"], routed_expert_slots=layers * cfg["num_experts"],
        assignment_slots=rows * cfg["num_experts_per_token"] * layers)

    def mean(name, scale=1.0):
        value = window_mean(obs["engine_report"], name)
        return None if value is None else scale * value

    harness.line("kda_mla_moe_work", parameters=params,
                 read_wait_ms_mean=mean("read_wait", 1e3),
                 host_gap_ms_mean=mean("host_gap", 1e3),
                 prefill_ms_mean=mean("prefill", 1e3),
                 experts_touched_mean=touched,
                 resident_tokens_mean=resident,
                 span_tokens_mean=mean("span_tokens"),
                 local_assignments_mean=mean("local_assignments"),
                 dispatch_rows_mean=mean("dispatch_rows"),
                 expert_bytes=counts.expert_bytes(cfg),
                 latent_bytes_per_token=counts.latent_bytes_per_token(cfg),
                 row_state_bytes=counts.row_state_bytes(cfg), **obs["work"])
    return res
