"""Job ``serve_linear_moe``: job ``serve`` as it stands, for a
linear-attention expert decoder that holds a share of its experts,
whose decode step costs what the routing made it cost and what its rows
keep. After ``serve.run`` it puts this model's own counts into
``obs["work"]`` (``linear_moe_counts``), fed by the histograms the
engine keeps, ``experts_touched`` and ``resident_tokens``, between the
window's two reports:

- ``linear_moe_decode_bytes_per_step``: the least bytes a decode step
  had to move (``decode_step_roofline.linear_moe``);
- ``moe_experts_bytes_per_step``: the touched held experts' bytes alone
  (``moe_experts_roofline.serve``);
- ``kda_step_bytes_per_step``: every row's state and tail, read and
  written (``kda_step_roofline.serve``);
- ``kda_chunk_flops_per_prefill``: the chunked scan's contractions for
  one prefill bucket (``kda_chunk_roofline.serve``);
- ``linear_moe_decode_flops_per_step``, for the line only.

The dense count ``decode_bytes_per_step`` that ``serve`` leaves there
(K and V in every layer, every weight once) is not this model's and is
taken out. A program without the histograms leaves the work that needs
them out, and the metrics that read it are left out of the line.

It also holds the net to the reference more sharply than 128 served
tokens can (``check_path``; the reference's docstring has the limits
and their readings). After the run, on the net it built and ``serve``
served, for ONE seeded sequence as long as the cell's prefill bucket:

- the SERVED PATH's logits, teacher-forced (``builder.served_path``:
  one bucketed prefill a decode row, each at a length of its own, each
  adopted into the row's pages, state and tail, then paged one-token
  steps over all rows with the row state carried), against the
  reference's at the same positions;
- the row state a prefill of the whole sequence leaves, and the state
  of the row whose decode steps end at the sequence's end, against the
  reference's final state, every KDA layer and head: what a state
  adopted, frozen or stepped WRONGLY moves;
- the program's two state kernels alone (``builder.kda_kernel_state``:
  the chunked scan, then one-token updates, the state kept in the
  row's own array between them) on the reference's own float32 q, k,
  v, decay and beta of one layer, against the reference's recurrence
  on the same numbers: the STATE's precision, which no number above
  can hold (each carries the bf16 activations' own rounding, and a
  state rounded to bfloat16 at every step adds less than that);
- the program's expert FFN (the router over every expert, the dispatch
  over the held share, the shared expert) on the reference's own FFN
  inputs, against the reference's on the same inputs and the same
  share: the outputs, and the chosen experts.

``correct`` is the served tokens' verdict AND these.
"""
from __future__ import annotations

import gc
import types

import numpy as np

from benchmarks import harness, linear_moe_counts as counts
from benchmarks.jobs import serve
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
            "linear_moe_decode_bytes_per_step":
                counts.decode_bytes_per_step(cfg, touched, resident, rows),
            "moe_experts_bytes_per_step": touched * counts.expert_bytes(cfg),
            "linear_moe_decode_flops_per_step":
                counts.decode_flops_per_step(cfg, rows, resident),
        })
    return work


def path_lengths(tokens, rows, steps, stride):
    """The prefill length of each decode row, ``stride`` apart from
    ``tokens - steps`` down: row 0's ``steps`` decode steps end at the
    sequence's end, where the reference's final state is known."""
    return tokens - steps - stride * np.arange(rows, dtype=np.int32)


def reference_side(ctx, weights):
    """What the comparisons need of the float32 reference, from
    ``weights`` (the net's, as the seed made them): one forward of one
    seeded sequence, its logits at the compared positions, every KDA
    layer's final state, and the expert FFN of every layer on its own
    input, that input rounded to the type the net is served in."""
    import jax.numpy as jnp

    cfg, ref, cell = ctx.config, ctx.reference, ctx.cell
    spec = cell["path_check"]
    tokens, rows = int(spec["tokens"]), int(cell["engine"]["max_batch_size"])
    steps = int(spec["steps"])
    ids = np.random.default_rng(ctx.seed + 2).integers(
        0, cfg["vocab_size"], tokens)
    lengths = path_lengths(tokens, rows, steps, int(spec["stride"]))
    decode_at = (lengths[None, :] + np.arange(steps)[:, None]).reshape(-1)
    at = np.concatenate([decode_at, lengths - 1])
    routing, states = [], {}
    h = ref.hidden(weights, cfg, jnp.asarray(ids), routing, states)
    want = np.asarray(ref.head(
        h[jnp.asarray(at)], weights["model.norm.weight"],
        weights["lm_head.weight"], eps=float(cfg["rms_norm_eps"])))
    served = weights["model.norm.weight"].dtype
    ffn_rows = np.linspace(0, tokens - 1, int(spec["ffn_rows"])).astype(
        np.int32)
    ffn = {}
    for index, (_, _, ffn_in) in enumerate(routing):
        h_in = ffn_in[jnp.asarray(ffn_rows)].astype(served)
        prefix = f"model.layers.{index}.mlp."
        routed, shared, chosen, margin = ref.expert_ffn(
            h_in, {k[len(prefix):]: v for k, v in weights.items()
                   if k.startswith(prefix)}, moe=ref.moe_static(cfg),
            share=ref.share_of(cfg))
        ffn[index] = (h_in, np.asarray(routed + shared), np.asarray(chosen),
                      np.asarray(margin))
    # the last KDA layer's recurrence alone, on its own float32 inputs
    last = max(states)
    lin, prefix = cfg["linear_attn_config"], f"model.layers.{last}.mixer."
    fed, fed_state = ref.kda_inputs_and_state(
        states[last][1], {k[len(prefix):]: v for k, v in weights.items()
                          if k.startswith(prefix)},
        heads=lin["num_heads"], dim=lin["head_dim"],
        neg_eigval=bool(cfg["kda_allow_neg_eigval"]))
    return {"ids": ids, "lengths": lengths, "steps": steps, "want": want,
            "fed": tuple(np.asarray(a) for a in fed),
            "fed_state": np.asarray(fed_state),
            "states": {i: np.asarray(s) for i, (s, _) in states.items()},
            "ffn": ffn}


def served_side(ctx, net, side):
    """The program's numbers beside ``side``'s: the relative error a
    position of the served path's decode logits and of its prefills'
    own rows, the state's relative error a head, the expert FFN's a
    token, and the share of decided tokens that went to another set of
    experts."""
    ref, builder, engine = ctx.reference, ctx.builder, ctx.cell["engine"]
    first, decoded, whole, stepped = builder.served_path(
        net, engine, side["ids"], side["lengths"], side["steps"])
    n = decoded.shape[0] * decoded.shape[1]
    decode_err = ref.relative_logit_errors(
        decoded.reshape(n, -1), side["want"][:n])
    prefill_err = ref.relative_logit_errors(first, side["want"][n:])
    # a KDA layer's row arrays are its state, then its tail; row 0's
    # steps end where the sequence does
    want_states = [side["states"][i] for i in sorted(side["states"])]
    state_err, stepped_err = (np.concatenate([
        ref.state_errors(got, want)
        for got, want in zip(arrays[0::2], want_states)])
        for arrays in (whole, stepped))
    kernel_err = ref.state_errors(
        builder.kda_kernel_state(net, side["fed"], side["steps"],
                                 engine["cache_dtype"]), side["fed_state"])
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
            "state_err": state_err, "stepped_state_err": stepped_err,
            "kernel_state_err": kernel_err,
            "ffn_err": np.concatenate(ffn_err),
            "route_elsewhere": elsewhere / max(decided, 1),
            "route_decided": decided}


def judge(ctx, got):
    """The eight readings beside their limits, on one line; True where
    all hold."""
    ref = ctx.reference
    d, p, s, t, k, f = (got[k] for k in (
        "decode_err", "prefill_err", "state_err", "stepped_state_err",
        "kernel_state_err", "ffn_err"))
    readings = {
        "path_err_median": (float(np.median(d)), ref.PATH_ERR),
        "path_err_p90": (float(np.percentile(d, 90)), ref.PATH_ERR_P90),
        "prefill_err_median": (float(np.median(p)), ref.PATH_ERR),
        "state_err_median": (float(np.median(s)), ref.PATH_STATE_ERR),
        "stepped_state_err_median": (float(np.median(t)),
                                     ref.PATH_STATE_ERR),
        "kernel_state_err_max": (float(k.max()), ref.KERNEL_STATE_ERR),
        "ffn_err_p90": (float(np.percentile(f, 90)), ref.FFN_ERR),
        "route_elsewhere": (got["route_elsewhere"], ref.ROUTE_ELSEWHERE),
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
        ffn_err_median=float(np.median(f)), ffn_err_max=float(f.max()),
        route_decided=got["route_decided"], ok=bool(ok))
    return bool(ok)


def check_path(ctx, net):
    side = reference_side(ctx, ctx.builder.weights(net))
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
    harness.note("serve_linear_moe: the served path's logits, the row "
                 "state and the expert layers against the reference's")
    res["correct"] = bool(check_path(ctx, built[0]) and res["correct"])
    obs = res["obs"]
    touched = window_mean(obs["engine_report"], "experts_touched")
    resident = window_mean(obs["engine_report"], "resident_tokens")
    local = window_mean(obs["engine_report"], "local_assignments")
    obs["work"].pop("decode_bytes_per_step", None)
    obs["work"].update(step_work(
        cfg, obs["engine"], int(cell["path_check"]["tokens"]), touched,
        resident))
    rows, layers = int(obs["engine"]["max_batch_size"]), \
        cfg["num_hidden_layers"]
    # what experts_touched.serve and local_assignments.serve are shares of
    obs["engine"] = dict(
        obs["engine"], routed_expert_slots=layers * cfg["n_routed_experts"],
        assignment_slots=rows * cfg["num_experts_per_tok"] * layers)
    def ms(name):
        mean = window_mean(obs["engine_report"], name)
        return None if mean is None else 1e3 * mean

    harness.line("linear_moe_work", parameters=params,
                 read_wait_ms_mean=ms("read_wait"),
                 host_gap_ms_mean=ms("host_gap"),
                 prefill_ms_mean=ms("prefill"),
                 experts_touched_mean=touched,
                 resident_tokens_mean=resident,
                 local_assignments_mean=local,
                 expert_bytes=counts.expert_bytes(cfg),
                 kv_bytes_per_token=counts.kv_bytes_per_token(cfg),
                 row_state_bytes=counts.row_state_bytes(cfg), **obs["work"])
    return res
