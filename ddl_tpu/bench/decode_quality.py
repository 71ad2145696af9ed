"""Quality bound for the int8 serving path: what does quantization cost?

The int8 levers (``ops/quant.py``) halve decode HBM traffic; this tool
pins what they cost in output quality, on REAL trained weights (any
``train_lm.py`` snapshot + its corpus):

1. **Held-out ppl delta** (weight-only int8): teacher-forced CE over the
   corpus's held-out tail through the standard eval path, f32/bf16
   params vs ``quantize_lm_params`` — the weight-quant quality bound.
2. **Greedy token agreement** (KV + weight int8): greedy generations
   from held-out prompts, bf16 generator vs ``kv`` vs ``kv+w`` —
   position-wise token match rate, plus the first-divergence histogram.
   (Greedy decode amplifies near-ties; agreement is the *strict* bound —
   a disagreement is usually an equally-likely token, not an error.)

Prints one JSON line per mode.

    python -m ddl_tpu.bench.decode_quality --checkpoint-dir ck --step N \
        --corpus corpus.npy --d-model 512 --layers 8
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checkpoint-dir", required=True)
    ap.add_argument("--job-id", default="lm")
    ap.add_argument("--step", type=int, required=True)
    ap.add_argument("--corpus", required=True, help="token .npy (byte-level)")
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--attn-window", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=256,
                    help="eval window length (must match training windows)")
    ap.add_argument("--eval-frac", type=float, default=0.05)
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=128)
    ap.add_argument("--gen-batches", type=int, default=4)
    ap.add_argument("--cpu-devices", type=int, default=0)
    args = ap.parse_args()

    if args.cpu_devices:
        from ddl_tpu.launch import force_cpu_devices

        force_cpu_devices(args.cpu_devices)
    import jax
    import jax.numpy as jnp
    import optax

    from ddl_tpu.checkpoint import load_params
    from ddl_tpu.data.lm_corpus import TokenCorpus
    from ddl_tpu.infer import make_lm_generator
    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.ops.quant import quantize_lm_params
    from ddl_tpu.parallel.sharding import LMMeshSpec, build_lm_mesh
    from ddl_tpu.train.lm_steps import LMTrainState, make_lm_step_fns
    from ddl_tpu.utils.compile_cache import activate_compile_cache

    activate_compile_cache()
    cfg = LMConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=args.heads,
        n_kv_heads=args.kv_heads,
        attn_window=args.attn_window,
        head_dim=args.d_model // args.heads,
        d_ff=4 * args.d_model,
        compute_dtype=(
            "bfloat16" if jax.default_backend() != "cpu" else "float32"
        ),
        remat=False,
    )
    spec = LMMeshSpec()
    mesh = build_lm_mesh(spec)
    # params-only restore: the skeleton comes from the snapshot's own
    # metadata, so any optimizer chain/schedule the training run used is
    # irrelevant here
    # vocab_size resolves a format-less snapshot's lm_head orientation
    params = load_params(
        args.checkpoint_dir, args.job_id, args.step, vocab_size=cfg.vocab_size
    )
    from ddl_tpu.parallel.lm_pipeline import saved_pipe_stages

    if saved_pipe_stages(params) > 1:
        raise SystemExit(
            "this snapshot is in the pipeline-parallel layout; "
            "decode_quality restores params only and does not "
            "restructure stages — resume it once with --pipe 1 (or "
            "decode via examples/generate_lm.py, which converts the "
            "layout) and point this tool at the re-saved snapshot"
        )
    qparams = quantize_lm_params(params)

    # --- held-out ppl: exact vs weight-only int8 -------------------------
    corpus = TokenCorpus(args.corpus, args.seq_len)
    _, eval_view = corpus.split(args.eval_frac)
    fns = make_lm_step_fns(
        cfg, spec, optax.adam(1e-3), jax.random.key(0), args.batch,
        args.seq_len,
    )
    n_eval = min(args.eval_batches, len(eval_view) // args.batch)
    if n_eval < 1:
        raise SystemExit(
            f"held-out split has {len(eval_view)} windows < one batch of "
            f"{args.batch}; grow --eval-frac or shrink --batch"
        )

    def heldout_ce(p) -> float:
        # evaluate only reads .params; a placeholder opt_state suffices
        st = LMTrainState(
            step=jnp.zeros((), jnp.int32), params=p, opt_state=()
        )
        ces = []
        for bi in range(n_eval):
            idx = range(bi * args.batch, (bi + 1) * args.batch)
            inp = np.stack([eval_view[i][0] for i in idx])
            tgt = np.stack([eval_view[i][1] for i in idx])
            m = fns.evaluate(st, jnp.asarray(inp), jnp.asarray(tgt))
            ces.append(float(m["ce"]))
        return float(np.mean(ces))

    ce_ref = heldout_ce(params)
    ce_q = heldout_ce(qparams)
    print(json.dumps({
        "metric": "heldout_ppl",
        "exact": round(float(np.exp(ce_ref)), 4),
        "int8_weights": round(float(np.exp(ce_q)), 4),
        "ppl_delta_pct": round(
            100 * (np.exp(ce_q) / np.exp(ce_ref) - 1), 3
        ),
        "eval_tokens": n_eval * args.batch * args.seq_len,
    }), flush=True)

    # --- greedy agreement: bf16 vs kv vs kv+w ----------------------------
    gen_exact = make_lm_generator(
        cfg, spec, prompt_len=args.prompt_len, max_new=args.max_new,
        batch=args.batch,
    )
    gen_kvq = make_lm_generator(
        cfg, spec, prompt_len=args.prompt_len, max_new=args.max_new,
        batch=args.batch, kv_quant=True,
    )
    gens = {
        "none": (gen_exact, params),
        "kv": (gen_kvq, params),
        # weight quant needs no generator flag — same compiled program,
        # int8 tree (QDense sniffs the scales)
        "kv+w": (gen_kvq, qparams),
    }
    outs = {k: [] for k in gens}
    gen_batches = min(args.gen_batches, len(eval_view) // args.batch)
    for bi in range(gen_batches):
        idx = range(bi * args.batch, (bi + 1) * args.batch)
        prompts = jnp.asarray(
            np.stack([eval_view[i][0][: args.prompt_len] for i in idx]),
            jnp.int32,
        )
        for k, (g, p) in gens.items():
            outs[k].append(np.asarray(g(p, prompts)))
    ref = np.concatenate(outs["none"])
    for k in ("kv", "kv+w"):
        got = np.concatenate(outs[k])
        match = (got == ref).mean()
        # first divergence per sequence (max_new = fully agreed)
        div = np.where(
            (got != ref).any(1),
            (got != ref).argmax(1),
            args.max_new,
        )
        print(json.dumps({
            "metric": "greedy_agreement",
            "quant": k,
            "token_match_rate": round(float(match), 4),
            "sequences": int(ref.shape[0]),
            "max_new": args.max_new,
            "median_first_divergence": int(np.median(div)),
            "fully_agreed_frac": round(float((div == args.max_new).mean()), 4),
        }), flush=True)


if __name__ == "__main__":
    main()
