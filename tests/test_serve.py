"""Continuous-batching serving engine (`ddl_tpu/serve/`).

Host tier (no JAX): block allocator invariants, scheduler admission
order / retire-and-recycle / watermarks, shed-policy determinism, the
ServingStats falsy-0.0 regression, the incremental tail-cursor cache,
and the new `obs diff` serving gates over synthetic streams.

Device tier (CPU JAX): paged-pool write/gather equivalence against a
contiguous reference, and the acceptance e2e — N concurrent clients
through the engine produce bit-identical tokens to N sequential
`make_lm_generator` runs (greedy, sampled, and int8-KV), with
recompiles bounded by the bucket grid and counted via obs events.
"""

import json
import os
import sys
import time

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# host tier: geometry helpers
# ---------------------------------------------------------------------------


def test_blocks_for_and_buckets():
    from ddl_tpu.serve.engine import pow2_at_least, pow2_at_most, prompt_bucket
    from ddl_tpu.serve.kv_pool import blocks_for

    assert blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2
    with pytest.raises(ValueError):
        blocks_for(0, 8)

    # smallest power-of-two multiple of block_size >= prompt_len
    assert prompt_bucket(1, 8) == 8
    assert prompt_bucket(8, 8) == 8
    assert prompt_bucket(9, 8) == 16
    assert prompt_bucket(17, 8) == 32
    assert prompt_bucket(5, 4) == 8
    with pytest.raises(ValueError):
        prompt_bucket(0, 8)

    assert [pow2_at_most(n) for n in (1, 2, 3, 7, 8, 9)] == [1, 2, 2, 4, 8, 8]
    assert [pow2_at_least(n) for n in (1, 2, 3, 7, 8, 9)] == [
        1, 2, 4, 8, 8, 16,
    ]


# ---------------------------------------------------------------------------
# host tier: block allocator
# ---------------------------------------------------------------------------


def test_allocator_invariants():
    from ddl_tpu.serve.kv_pool import BlockAllocator, PoolExhausted

    a = BlockAllocator(8, 4)
    x = a.alloc(3)
    y = a.alloc(2)
    # a block is never handed out twice
    assert len(set(x) & set(y)) == 0
    assert a.free_blocks + a.used_blocks == 8
    assert a.high_water == 5
    assert not a.can_alloc(4)
    with pytest.raises(PoolExhausted):
        a.alloc(4)
    a.free(x)
    assert a.free_blocks == 6
    # freeing twice is a bookkeeping bug, loudly
    with pytest.raises(ValueError):
        a.free(x)
    # lowest-id-first: recycled low ids come back before fresh high ids
    z = a.alloc(3)
    assert z == sorted(z) == [0, 1, 2]
    assert a.free_blocks + a.used_blocks == 8
    assert a.high_water == 5  # peak, not current


def test_allocator_fragmentation_and_compaction():
    from ddl_tpu.serve.kv_pool import BlockAllocator

    a = BlockAllocator(8, 4)
    x = a.alloc(2)  # [0, 1]
    y = a.alloc(2)  # [2, 3]
    z = a.alloc(2)  # [4, 5]
    assert a.fragmentation() == 0.0
    assert a.compaction_plan() is None
    a.free(y)
    # live span [0, 5] holds 4 blocks -> 1/3 holes
    assert a.fragmentation() == pytest.approx(1 - 4 / 6)
    plan = a.compaction_plan()
    # packs live blocks to lowest ids, preserving relative order
    assert plan == {4: 2, 5: 3}
    a.commit_plan(plan)
    assert a.fragmentation() == 0.0
    assert sorted(a._refs) == [0, 1, 2, 3]
    assert all(a.refcount(b) == 1 for b in range(4))
    assert a.free_blocks == 4
    del x, z


# ---------------------------------------------------------------------------
# host tier: scheduler
# ---------------------------------------------------------------------------


def _req(rid, prompt_len=8, max_new=4, **kw):
    from ddl_tpu.serve.scheduler import Request

    return Request(
        id=rid, prompt=np.zeros(prompt_len, np.int32), max_new=max_new, **kw
    )


def test_scheduler_admission_order_and_retire_recycle():
    from ddl_tpu.serve.kv_pool import BlockAllocator
    from ddl_tpu.serve.scheduler import ContinuousScheduler

    alloc = BlockAllocator(8, 8)
    s = ContinuousScheduler(alloc, max_batch=2, max_blocks_per_seq=4)
    a = s.try_admit(_req("a", 8, 8))   # 2 blocks
    b = s.try_admit(_req("b", 8, 8))   # 2 blocks
    assert (a.lane, b.lane) == (0, 1)  # lanes bound in admission order
    assert s.try_admit(_req("c")) is None  # no free lane
    # retire-and-recycle: blocks return and the freed lane rebinds
    freed = set(a.block_ids)
    s.retire(a.lane)
    assert alloc.free_blocks == 6
    c = s.try_admit(_req("c", 8, 8))
    assert c.lane == 0
    assert set(c.block_ids) == freed  # lowest-first recycles the hole
    s.retire(0)
    with pytest.raises(ValueError):
        s.retire(0)  # retiring an idle lane is a bookkeeping bug
    s.retire(1)
    assert alloc.used_blocks == 0


def test_scheduler_watermark_and_fits_ever():
    from ddl_tpu.serve.kv_pool import BlockAllocator
    from ddl_tpu.serve.scheduler import ContinuousScheduler

    alloc = BlockAllocator(4, 8)
    s = ContinuousScheduler(
        alloc, max_batch=4, max_blocks_per_seq=4, min_free_blocks=2
    )
    # needs 1 block but must leave 2 free: ok at 4 free, refused at 2
    assert s.can_admit(_req("a", 4, 4))
    s.try_admit(_req("a", 8, 8))  # 2 blocks -> 2 free
    assert not s.can_admit(_req("b", 4, 4))
    assert s.try_admit(_req("b", 4, 4)) is None
    # oversize request: impossible EVER, not merely now
    big = _req("big", 30, 8)  # 37 rows -> 5 blocks > max_blocks_per_seq
    assert not s.fits_ever(big)
    with pytest.raises(ValueError):
        s.try_admit(big)
    # fits the table but never the pool once the watermark is held
    # back: queueing it would livelock the drain loop (regression)
    alloc2 = BlockAllocator(4, 8)
    s2 = ContinuousScheduler(
        alloc2, max_batch=4, max_blocks_per_seq=8, min_free_blocks=2
    )
    never = _req("never", 20, 8)  # 28 rows -> 4 blocks; 4+2 > pool of 4
    assert not s2.fits_ever(never)
    assert s2.fits_ever(_req("ok", 8, 8))  # 2 blocks: 2+2 <= 4


def test_shed_policies_deterministic():
    from ddl_tpu.serve.admission import AdmissionController

    def drive(policy):
        sheds = []
        c = AdmissionController(
            max_queue=2, policy=policy,
            on_shed=lambda r, reason: sheds.append((r.id, reason)),
        )
        outcomes = [c.offer(_req(f"r{i}")) for i in range(4)]
        outcomes.append(c.offer(_req("huge"), fits_ever=False))
        return outcomes, sheds, [r.id for r in c.queue]

    # reject: new arrivals turned away, queue keeps the oldest
    out, sheds, q = drive("reject")
    assert out == ["queued", "queued", "rejected", "rejected", "rejected"]
    assert sheds == [
        ("r2", "queue_full"), ("r3", "queue_full"), ("huge", "too_large"),
    ]
    assert q == ["r0", "r1"]
    # shed_oldest: freshest-first under overload
    out, sheds, q = drive("shed_oldest")
    assert out == [
        "queued", "queued", "queued_shed_oldest", "queued_shed_oldest",
        "rejected",
    ]
    assert sheds == [
        ("r0", "queue_full"), ("r1", "queue_full"), ("huge", "too_large"),
    ]
    assert q == ["r2", "r3"]
    # determinism: the same pressure pattern sheds the same requests
    assert drive("shed_oldest") == drive("shed_oldest")


# ---------------------------------------------------------------------------
# host tier: ServingStats falsy-zero regression + serving gates
# ---------------------------------------------------------------------------


def _decode_event(ts, **kw):
    e = dict(
        kind="decode", ts=ts, request_id="r", prompt_len=8, new_tokens=4,
        batch=1, dur=0.1, tok_per_s=40.0, warm=True, chips=2,
    )
    e.update(kw)
    return e


def test_serving_stats_zero_values_are_present():
    """queue_delay_s=0.0 / ttft_s=0.0 are measurements, not gaps — the
    falsy-drop regression this PR pins down."""
    from ddl_tpu.obs.serving import ServingStats

    events = [
        _decode_event(10.0, queue_delay=0.0, ttft=0.0),
        _decode_event(10.2, queue_delay=0.0, ttft=0.0),
        _decode_event(10.4, queue_delay=0.5, ttft=0.25),
    ]
    s = ServingStats.from_events(events).summary()
    pct = s["percentiles"]
    assert pct["queue_delay_s"]["count"] == 3
    assert pct["ttft_s"]["count"] == 3
    assert pct["queue_delay_s"]["p50"] == 0.0
    assert pct["ttft_s"]["p50"] == 0.0
    # warm-span aggregate: 12 warm tokens over [9.9, 10.4]
    assert s["agg_tok_per_s"] == pytest.approx(12 / 0.5)
    assert s["chips"] == 2
    assert s["agg_tok_per_s_per_chip"] == pytest.approx(12 / 0.5 / 2)


def test_agg_spans_per_engine_not_global():
    """A CI job stream holds a decode smoke and a serve-bench smoke
    minutes apart; the aggregate must sum per-engine activity windows,
    not stretch one span across the idle gap (regression: the gate
    would otherwise move with test ordering, not serving perf)."""
    from ddl_tpu.obs.serving import ServingStats

    events = [
        _decode_event(10.0),                      # one-shot decode
        _decode_event(10.1),                      # span [9.9, 10.1]
        _decode_event(300.0, engine="serve"),     # serve-bench, 5 min
        _decode_event(300.3, engine="serve"),     # later: [299.9, 300.3]
    ]
    s = ServingStats.from_events(events).summary()
    # 16 warm tokens over 0.2s + 0.4s of ACTIVITY, not over ~290s
    assert s["agg_tok_per_s"] == pytest.approx(16 / 0.6)
    # round-trips through the cursor sidecar state
    rt = ServingStats.from_state(ServingStats.from_events(events).state_dict())
    assert rt.summary() == s


def test_summarize_mean_rate_zero_not_dropped():
    """A cold-only stream whose tok_per_s is exactly 0.0 must still
    populate the legacy mean (absence, not falsiness, drops it)."""
    from ddl_tpu.obs.report import summarize_run

    events = [
        _decode_event(1.0, warm=False, tok_per_s=0.0),
        {"kind": "period", "period": 0},
    ]
    s = summarize_run(events)
    assert s["decode"]["mean_tok_per_s"] == 0.0


def _write_stream(log_dir, job, events, host=0):
    job_dir = log_dir / "by_job_id" / job  # report._job_dir layout
    job_dir.mkdir(parents=True, exist_ok=True)
    path = job_dir / f"events-h{host:03d}.jsonl"
    with open(path, "a") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return path


def test_cursor_incremental_matches_scratch(tmp_path):
    """The tail-cursor cache folds only appended bytes and matches a
    from-scratch rebuild exactly (same reservoir, same percentiles)."""
    from ddl_tpu.obs.cursor import CACHE_NAME, incremental_serving_stats

    job = tmp_path / "by_job_id" / "j1"
    rng = np.random.default_rng(0)
    evs = [
        _decode_event(float(i), ttft=float(rng.exponential(0.1)),
                      queue_delay=float(rng.exponential(0.05)))
        for i in range(40)
    ]
    _write_stream(tmp_path, "j1", evs[:25])
    s1 = incremental_serving_stats(tmp_path, "j1")
    assert s1.requests == 25
    assert (job / CACHE_NAME).exists()
    _write_stream(tmp_path, "j1", evs[25:])  # append the tail
    s2 = incremental_serving_stats(tmp_path, "j1")
    ref = incremental_serving_stats(tmp_path, "j1", cache=False)
    assert s2.requests == ref.requests == 40
    assert s2.summary() == ref.summary()
    # the cursor consumed the whole file: a third call reads 0 new bytes
    cursor = json.loads((job / CACHE_NAME).read_text())
    size = (job / "events-h000.jsonl").stat().st_size
    assert cursor["files"]["events-h000.jsonl"] == size


def test_cursor_torn_line_and_truncation(tmp_path):
    from ddl_tpu.obs.cursor import CACHE_NAME, incremental_serving_stats

    job = tmp_path / "by_job_id" / "j2"
    path = _write_stream(
        tmp_path, "j2", [_decode_event(1.0), _decode_event(2.0)]
    )
    # torn final line: stays un-consumed until completed
    with open(path, "a") as f:
        f.write('{"kind": "decode", "ts": 3.0, "new_')
    s = incremental_serving_stats(tmp_path, "j2")
    assert s.requests == 2
    with open(path, "a") as f:
        f.write('tokens": 4, "warm": true, "batch": 1}\n')
    s = incremental_serving_stats(tmp_path, "j2")
    assert s.requests == 3
    # truncation below the cursor: clean rebuild, never double-count
    with open(path, "w") as f:
        f.write(json.dumps(_decode_event(9.0)) + "\n")
    s = incremental_serving_stats(tmp_path, "j2")
    assert s.requests == 1
    assert (job / CACHE_NAME).exists()


def test_cursor_recreated_stream_rebuilds(tmp_path):
    """A stream deleted and re-created under the same name (re-used job
    id) must rebuild, not fold on top of the old run's accumulators —
    even when the new file is LARGER than the old cursor, where a pure
    size check passes (regression: head-fingerprint guard)."""
    from ddl_tpu.obs.cursor import incremental_serving_stats

    path = _write_stream(tmp_path, "j3", [_decode_event(1.0)])
    assert incremental_serving_stats(tmp_path, "j3").requests == 1
    # re-create, same name, MORE events than the old cursor consumed
    path.unlink()
    _write_stream(
        tmp_path, "j3", [_decode_event(float(t)) for t in range(5, 9)]
    )
    s = incremental_serving_stats(tmp_path, "j3")
    ref = incremental_serving_stats(tmp_path, "j3", cache=False)
    assert s.requests == ref.requests == 4  # not 1 + 4
    assert s.summary() == ref.summary()
    # a tracked stream that disappeared outright also rebuilds: the
    # surviving host's events must not ride on stale accumulators
    _write_stream(tmp_path, "j4", [_decode_event(1.0)], host=0)
    extra = _write_stream(tmp_path, "j4", [_decode_event(2.0)], host=1)
    assert incremental_serving_stats(tmp_path, "j4").requests == 2
    extra.unlink()
    assert incremental_serving_stats(tmp_path, "j4").requests == 1


def test_cursor_corrupt_sidecar_rebuilds(tmp_path):
    """A JSON-valid sidecar with the wrong inner shape must be
    discarded and rebuilt, not crash every summarize until an operator
    deletes it by hand (the module's stated contract)."""
    from ddl_tpu.obs.cursor import (
        CACHE_NAME, VERSION, incremental_serving_stats,
    )

    job = tmp_path / "by_job_id" / "j5"
    _write_stream(tmp_path, "j5", [_decode_event(1.0), _decode_event(2.0)])
    assert incremental_serving_stats(tmp_path, "j5").requests == 2
    (job / CACHE_NAME).write_text(json.dumps({
        "version": VERSION, "capacity": 4096, "files": {},
    }))  # passes _load_cache, breaks the stats restore
    s = incremental_serving_stats(tmp_path, "j5")
    assert s.requests == 2
    # and the rebuild repaired the sidecar in place
    assert incremental_serving_stats(tmp_path, "j5").requests == 2


def _run_obs(argv):
    from ddl_tpu.obs import report

    old = sys.argv
    sys.argv = ["obs"] + argv
    try:
        report.main()
    finally:
        sys.argv = old


def test_obs_diff_gates_ttft_and_aggregate(tmp_path, capsys):
    """`obs diff --fail-slowdown` gates p99 TTFT inflation and aggregate
    tokens/s/chip drops (the two serve-bench acceptance gates)."""
    evs = [
        _decode_event(
            10.0 + 0.1 * i, ttft=0.01 + 0.001 * i, queue_delay=0.0,
        )
        for i in range(20)
    ] + [{"kind": "period", "period": 0, "steps_per_s": 10.0, "steps": 1}]
    _write_stream(tmp_path, "serve", evs)
    base = tmp_path / "base.json"
    _run_obs([
        "baseline", "serve", "--log-dir", str(tmp_path), "--out", str(base),
    ])
    # run vs its own baseline: all gates pass, and say which ran
    _run_obs([
        "diff", "serve", "--log-dir", str(tmp_path),
        "--baseline", str(base), "--fail-slowdown", "0.5",
    ])
    ok_line = capsys.readouterr().out
    assert "OK" in ok_line
    # doctor the baseline: a much better p99 TTFT -> current run fails
    doctored = json.loads(base.read_text())
    doctored["summary"]["decode"]["percentiles"]["ttft_s"]["p99"] = 1e-5
    bad = tmp_path / "ttft.json"
    bad.write_text(json.dumps(doctored))
    with pytest.raises(SystemExit, match="p99 TTFT"):
        _run_obs([
            "diff", "serve", "--log-dir", str(tmp_path),
            "--baseline", str(bad), "--fail-slowdown", "0.5",
        ])
    # a much better aggregate tokens/s/chip -> current run fails
    doctored = json.loads(base.read_text())
    d = doctored["summary"]["decode"]
    d["agg_tok_per_s_per_chip"] = d["agg_tok_per_s_per_chip"] * 10
    bad = tmp_path / "agg.json"
    bad.write_text(json.dumps(doctored))
    with pytest.raises(SystemExit, match="tok/s/chip"):
        _run_obs([
            "diff", "serve", "--log-dir", str(tmp_path),
            "--baseline", str(bad), "--fail-slowdown", "0.5",
        ])


# ---------------------------------------------------------------------------
# device tier: paged pool vs contiguous reference
# ---------------------------------------------------------------------------


def _tiny_cfg(**kw):
    from ddl_tpu.models.transformer import LMConfig

    base = dict(
        vocab_size=256, d_model=64, n_layers=2, n_heads=8, head_dim=8,
        d_ff=256, compute_dtype="float32",
    )
    base.update(kw)
    return LMConfig(**base)


def _init_lm(cfg):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models.transformer import TransformerLM
    from ddl_tpu.parallel.sharding import LMMeshSpec

    params = nn.meta.unbox(
        TransformerLM(cfg, None).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    )
    return cfg, params, LMMeshSpec()


@pytest.fixture(scope="module")
def lm():
    """Tiny LM params shared by every engine test in this module."""
    return _init_lm(_tiny_cfg())


@pytest.mark.parametrize("quant", [False, True])
def test_kv_pool_write_gather_roundtrip(quant):
    """pool_write_prefill + pool_write_token + pool_gather reproduce a
    contiguous cache exactly, and cache_write_token lands each row at
    the same gathered index a fresh gather would show."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.ops.quant import QuantKV
    from ddl_tpu.serve.kv_pool import (
        cache_write_token,
        init_kv_pool,
        pool_gather,
        pool_write_prefill,
        pool_write_token,
    )

    cfg = _tiny_cfg(n_layers=1)
    bs, nb = 4, 8
    pools = init_kv_pool(cfg, nb, bs, quant=quant)
    pool = pools[0]
    hkv, dh = cfg.kv_heads, cfg.head_dim
    rng = np.random.default_rng(1)

    # one request: 6 prompt rows over blocks [2, 5], then 2 decoded rows
    prompt_k = jnp.asarray(rng.normal(size=(1, 8, hkv * dh)), jnp.float32)
    prompt_v = jnp.asarray(rng.normal(size=(1, 8, hkv * dh)), jnp.float32)
    if quant:
        from ddl_tpu.ops.quant import kv_unfuse, quantize_q8

        def fuse_cache(k4, v4):
            kq, ks = quantize_q8(k4)
            vq, vs = quantize_q8(v4)
            b, t = k4.shape[:2]
            return QuantKV(
                kq.reshape(b, t, -1), ks[..., 0].transpose(0, 2, 1),
                vq.reshape(b, t, -1), vs[..., 0].transpose(0, 2, 1),
            )

        cache = fuse_cache(
            prompt_k.reshape(1, 8, hkv, dh), prompt_v.reshape(1, 8, hkv, dh)
        )
        del kv_unfuse
    else:
        cache = (prompt_k, prompt_v)
    ids = jnp.asarray([2, 5], jnp.int32)
    pool = pool_write_prefill(pool, cache, ids)

    tables = jnp.asarray([[2, 5]], jnp.int32)
    gathered = pool_gather(pool, tables)
    if quant:
        ref = cache.kq[0]
        got = gathered.kq[0]
    else:
        ref, got = prompt_k[0], gathered[0][0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    # append one decoded row at length 6 (block 5, slot 2) both ways
    k_new = jnp.asarray(rng.normal(size=(1, 1, hkv, dh)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(1, 1, hkv, dh)), jnp.float32)
    pool2 = pool_write_token(
        pool, k_new, v_new, jnp.asarray([5]), jnp.asarray([2])
    )
    fresh = pool_gather(pool2, tables)
    appended = cache_write_token(gathered, k_new, v_new, jnp.asarray([6]))
    f1, f2 = jax.tree_util.tree_leaves(fresh), jax.tree_util.tree_leaves(
        appended
    )
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # idle-lane drop: out-of-range block id leaves the pool untouched
    pool3 = pool_write_token(
        pool2, k_new, v_new, jnp.asarray([nb]), jnp.asarray([0])
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(pool2), jax.tree_util.tree_leaves(pool3)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# device tier: the engine e2e (acceptance)
# ---------------------------------------------------------------------------


def _sequential_tokens(cfg, spec, params, clients, seed, **gen_kw):
    import jax
    import jax.numpy as jnp

    from ddl_tpu.infer.decode import make_lm_generator

    out, gens = {}, {}
    for cid, prompt, mn in clients:
        key = (len(prompt), mn)
        if key not in gens:
            gens[key] = make_lm_generator(
                cfg, spec, prompt_len=len(prompt), max_new=mn, batch=1,
                **gen_kw,
            )
        toks = gens[key](
            params, jnp.asarray(prompt[None, :]), jax.random.PRNGKey(seed)
        )
        out[cid] = np.asarray(toks)[0]
    return out


def _clients(n, rng, lo=5, hi=20, new_lo=4, new_hi=12):
    return [
        (
            f"c{i}",
            rng.integers(0, 256, int(rng.integers(lo, hi))).astype(np.int32),
            int(rng.integers(new_lo, new_hi)),
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("block", ["gpt", "afmoe_capacity"])
def test_engine_matches_sequential_decode(lm, block):
    """THE acceptance e2e: 8 concurrent clients, mixed prompt/output
    lengths, bit-identical to 8 one-at-a-time LMDecode runs.  The second
    block is what a second serving stack could not run: q/k norms, the
    output gate, four norms, gated MLPs, the embedding multiplier and a
    dense layer before a capacity-routed expert layer (capacity E/k: no
    choice is dropped, so the padded prefill routes as the unpadded)."""
    from ddl_tpu.serve.engine import ServeEngine

    if block == "afmoe_capacity":
        lm = _init_lm(_tiny_cfg(
            qk_norm=True, attn_gate=True, sandwich_norm=True, mlp_gated=True,
            embed_scale=True, num_experts=4, expert_top_k=2,
            num_dense_layers=1, capacity_factor=2.0,
        ))
        assert "mlp" in lm[1]["block0"] and "moe" in lm[1]["block1"]
    cfg, params, spec = lm
    clients = _clients(8, np.random.default_rng(7))
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=64,
                      max_batch=8)
    for cid, prompt, mn in clients:
        eng.submit(prompt, mn, request_id=cid, rng_seed=3)
    got = eng.run()
    want = _sequential_tokens(cfg, spec, params, clients, seed=3)
    assert set(got) == set(want)
    for cid in want:
        np.testing.assert_array_equal(got[cid], want[cid])
        assert eng.outcomes[cid] == "ok"
    assert eng.stats["completed"] == 8
    # every lane retired, every block recycled
    assert eng.allocator.used_blocks == 0
    assert not eng.busy


@pytest.mark.parametrize(
    "kw",
    [dict(temperature=0.8, top_k=17), dict(kv_quant=True)],
    ids=["sampled", "quant_kv"],
)
def test_engine_matches_sequential_variants(lm, kw):
    """Same RNG split sequence as the fused generator (sampled), and the
    int8 pool path (ops.quant.QuantKV) — still token-exact."""
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    clients = _clients(4, np.random.default_rng(3))
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=64,
                      max_batch=4, **kw)
    for cid, prompt, mn in clients:
        eng.submit(prompt, mn, request_id=cid, rng_seed=11)
    got = eng.run()
    want = _sequential_tokens(cfg, spec, params, clients, seed=11, **kw)
    for cid in want:
        np.testing.assert_array_equal(got[cid], want[cid])


def test_engine_max_new_one(lm):
    """A request done straight out of admission (max_new=1: the
    prefill's sampled token is the whole output) must not crash the
    decode chunk-length computation or stall the batch behind it
    (regression: remaining=0 reached pow2_at_most)."""
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    clients = [
        ("one", np.arange(6, dtype=np.int32), 1),
        ("few", np.arange(9, dtype=np.int32), 5),
    ]
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=32,
                      max_batch=4)
    for cid, prompt, mn in clients:
        eng.submit(prompt, mn, request_id=cid, rng_seed=2)
    got = eng.run()
    want = _sequential_tokens(cfg, spec, params, clients, seed=2)
    for cid in want:
        np.testing.assert_array_equal(got[cid], want[cid])
    assert len(got["one"]) == 1
    assert eng.allocator.used_blocks == 0 and not eng.busy


def test_bucket_bounded_recompiles_counted_via_obs(lm, tmp_path):
    """Prompts inside one bucket share a prefill program; admits/retires
    never rebuild the decode program; every compile is visible both in
    engine stats and in the emitted obs events."""
    from ddl_tpu.obs import EventWriter
    from ddl_tpu.obs.report import load_run
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    obs = EventWriter(tmp_path, "serve-test")
    # prefix cache off: this test pins the BUCKETED full-prefill program
    # accounting, and these arange prompts share full-block prefixes
    # that would otherwise (correctly) divert admits to the chunk path
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=64,
                      max_batch=4, max_steps_per_dispatch=4, obs=obs,
                      prefix_cache=False)
    # lens 3..8 share bucket 8; lens 9..15 bucket 16
    clients = [
        ("a", np.arange(1, 6, dtype=np.int32), 6),    # bucket 8
        ("b", np.arange(1, 9, dtype=np.int32), 6),    # bucket 8 (shared)
        ("c", np.arange(1, 13, dtype=np.int32), 6),   # bucket 16
        ("d", np.arange(1, 4, dtype=np.int32), 6),    # bucket 8 (shared)
    ]
    for cid, prompt, mn in clients:
        eng.submit(prompt, mn, request_id=cid)
    eng.run()
    obs.close()
    assert eng.stats["prefill_compiles"] == 2  # one per bucket, not per req
    # decode grid is log x log: k in {1,2,4}, nmax in {1,2} here
    assert eng.stats["decode_compiles"] <= 6
    assert eng.stats["decode_steps"] < eng.stats["decode_compiles"] * 100

    events = load_run(tmp_path, "serve-test")
    kinds = [e["kind"] for e in events]
    assert kinds.count("serve_admit") == 4
    assert kinds.count("serve_retire") == 4
    assert kinds.count("decode") == 4
    assert "kv_pool_stats" in kinds
    admits = [e for e in events if e["kind"] == "serve_admit"]
    # the compiled flag marks exactly the first admit of each bucket
    assert [a["compiled"] for a in admits] == [True, False, True, False]
    # pool stats reach zero-used after the last retire
    last = [e for e in events if e["kind"] == "kv_pool_stats"][-1]
    assert last["used"] == 0 and last["active_lanes"] == 0
    # per-request decode events carry the serving fields, 0.0 included
    d = [e for e in events if e["kind"] == "decode"][0]
    assert d["engine"] == "serve"
    assert d["ttft"] is not None and d["queue_delay"] >= 0.0


def test_shed_under_pressure_e2e(lm, tmp_path):
    """Overload against a 1-lane engine with a 2-deep queue: admission
    control sheds deterministically, the rest complete exactly."""
    from ddl_tpu.obs import EventWriter
    from ddl_tpu.obs.report import load_run
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    clients = _clients(5, np.random.default_rng(5), new_lo=3, new_hi=6)

    def drive(policy):
        obs = EventWriter(tmp_path / policy, "shed-test")
        eng = ServeEngine(
            cfg, params, spec, block_size=8, num_blocks=16, max_batch=1,
            max_queue=2, policy=policy, obs=obs,
        )
        outcomes = [
            eng.submit(prompt, mn, request_id=cid)
            for cid, prompt, mn in clients
        ]
        got = eng.run()
        obs.close()
        sheds = [
            (e["request_id"], e["reason"])
            for e in load_run(tmp_path / policy, "shed-test")
            if e["kind"] == "serve_shed"
        ]
        return outcomes, got, sheds, eng

    outcomes, got, sheds, eng = drive("reject")
    assert outcomes == ["queued"] * 2 + ["rejected"] * 3
    assert sheds == [("c2", "queue_full"), ("c3", "queue_full"),
                     ("c4", "queue_full")]
    assert sorted(got) == ["c0", "c1"]
    assert eng.stats["shed"] == 3
    want = _sequential_tokens(cfg, spec, params, clients[:2], seed=0)
    for cid in want:
        np.testing.assert_array_equal(got[cid], want[cid])

    outcomes, got, sheds, eng = drive("shed_oldest")
    assert outcomes == ["queued"] * 2 + ["queued_shed_oldest"] * 3
    # c0/c1 queued first; c2..c4 push out the oldest queued each time
    assert sheds == [("c0", "queue_full"), ("c1", "queue_full"),
                     ("c2", "queue_full")]
    assert sorted(got) == ["c3", "c4"]
    assert eng.outcomes["c0"] == "shed:queue_full"


def test_defrag_compacts_and_preserves_tokens(lm):
    """Retiring the middle request fragments the pool; defrag moves live
    blocks device-side and rewrites tables — decode continues exactly."""
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    rng = np.random.default_rng(9)
    short = ("mid", rng.integers(0, 256, 8).astype(np.int32), 3)
    longs = [
        (f"l{i}", rng.integers(0, 256, 8).astype(np.int32), 12)
        for i in range(2)
    ]
    eng = ServeEngine(cfg, params, spec, block_size=4, num_blocks=16,
                      max_batch=3, max_steps_per_dispatch=1)
    eng.submit(*longs[0][1:], request_id=longs[0][0])
    eng.submit(*short[1:], request_id=short[0])
    eng.submit(*longs[1][1:], request_id=longs[1][0])
    # run until the short middle request retires, leaving a hole
    while "mid" not in eng.results:
        eng.step()
    assert eng.allocator.fragmentation() > 0.0
    moved = eng.defrag()
    assert moved
    assert eng.allocator.fragmentation() == 0.0
    eng.run()
    want = _sequential_tokens(
        cfg, spec, params, [short] + longs, seed=0
    )
    for cid in want:
        np.testing.assert_array_equal(eng.results[cid], want[cid])


def test_jit_cache_size_is_there_to_count_compiles():
    """The engine's compile counters lean on the private
    ``PjitFunction._cache_size`` of the one supported installation: pin
    that it exists and counts executables, so an upgrade that moves it
    fails here and not as a silent zero in serving stats."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.serve.engine import _jit_compiles

    prog = jax.jit(lambda x: x + 1)
    assert _jit_compiles(prog) == 0
    prog(jnp.zeros((2,)))
    prog(jnp.ones((2,)))
    assert _jit_compiles(prog) == 1
    prog(jnp.zeros((3,)))
    assert _jit_compiles(prog) == 2


def test_engine_precompile_covers_grid(lm):
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=32,
                      max_batch=2, max_steps_per_dispatch=2)
    counts = eng.precompile(12, 8)
    # buckets {8, 16}; ks {1, 2}; nmaxes pow2-ceil over 1..3 -> {1, 2, 4};
    # chunk grid (prefix cache on by default): (mid + final) x {8, 16}
    # at the single clamped view width (mid is reachable without a
    # chunk bound: the view clamp can split a prefix-hit tail)
    assert counts == {"prefill": 2, "decode": 6, "chunk": 4}
    # second call: everything cached
    assert eng.precompile(12, 8) == {"prefill": 0, "decode": 0, "chunk": 0}
    # a request inside the envelope then compiles NOTHING new
    eng.submit(np.arange(1, 11, dtype=np.int32), 8, request_id="r")
    eng.run()
    assert eng.stats["prefill_compiles"] == 0
    assert eng.stats["decode_compiles"] == 0


def test_engine_sharded_mesh_smoke(lm):
    """data=2/model=2 sim mesh: the contract-probed sharded program
    actually runs and retires (numerics covered by the 1-device
    exactness tests; resharded reductions may round differently)."""
    import jax

    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.serve.engine import ServeEngine

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 sim devices")
    cfg, params, _ = lm
    eng = ServeEngine(
        cfg, params, LMMeshSpec(data=2, model=2), block_size=8,
        num_blocks=32, max_batch=4,
    )
    for i in range(4):
        eng.submit(np.arange(1, 9, dtype=np.int32), 5, request_id=f"c{i}")
    got = eng.run()
    assert sorted(got) == [f"c{i}" for i in range(4)]
    assert all(len(v) == 5 for v in got.values())
    assert eng.allocator.used_blocks == 0


def test_serve_bench_cli_report_and_obs(lm, tmp_path, capsys):
    """serve-bench end-to-end at toy scale: the report renders, the obs
    stream round-trips through `obs summarize`, and warm percentiles
    include a real TTFT."""
    from ddl_tpu.serve import bench

    log_dir = tmp_path / "logs"
    # fixed lengths + 2 lanes: wave 1 pays every compile (cold), the
    # following 3 waves reuse the programs -> warm percentiles without
    # the (slow) full-grid precompile
    bench.main([
        "--clients", "8", "--prompt-len", "8", "--max-new", "4",
        "--block-size", "8", "--num-blocks", "32", "--max-batch", "2",
        "--steps-per-dispatch", "4", "--no-warmup",
        "--obs-log-dir", str(log_dir), "--job-id", "sb-test",
    ])
    out = capsys.readouterr().out
    assert "== serve-bench report ==" in out
    assert "completed: 8" in out
    assert "aggregate:" in out
    assert "-- percentiles (warm requests) --" in out
    _run_obs(["summarize", "sb-test", "--log-dir", str(log_dir)])
    out = capsys.readouterr().out
    assert "decode: 8 requests" in out
    assert "ttft_s" in out
    assert "serving aggregate:" in out


@pytest.mark.skipif(
    not os.environ.get("DDL_SERVE_PERF"),
    reason="perf acceptance: set DDL_SERVE_PERF=1 (the verify skill "
    "serve-bench smoke); wall-clock sensitive, excluded from tier-1",
)
def test_serve_bench_beats_sequential(capsys):
    """Acceptance: at a weight-streaming-bound size the continuous batch
    beats one-request-at-a-time throughput at equal settings."""
    from ddl_tpu.serve import bench

    bench.main([
        "--clients", "8", "--prompt-len", "8:24", "--max-new", "16:32",
        "--block-size", "8", "--num-blocks", "64",
        "--d-model", "512", "--layers", "2", "--heads", "8",
        "--compare-sequential",
    ])
    out = capsys.readouterr().out
    line = [l for l in out.splitlines() if "sequential baseline" in l][0]
    ratio = float(line.rsplit("x", 1)[1])
    assert ratio > 1.0, line


def test_warmup_excluded_from_stats(lm):
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=32,
                      max_batch=2)
    eng.warmup(8, max_new=2)
    assert eng.stats["submitted"] == 0
    assert eng.stats["completed"] == 0
    assert "_warmup" not in eng.results
    assert all(r["request_id"] != "_warmup" for r in eng.request_log)
    # warmed bucket serves without a NEW prefill compile (the warmup's
    # own compile stays counted — it is a real compile)
    before = eng.stats["prefill_compiles"]
    eng.submit(np.arange(1, 9, dtype=np.int32), 3, request_id="r")
    eng.run()
    assert eng.stats["prefill_compiles"] == before


def test_request_log_feeds_serving_stats(lm):
    """The engine's in-memory request log is event-shaped: ServingStats
    builds the same percentile table obs summarize would."""
    from ddl_tpu.obs.serving import ServingStats
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    # prefix cache off: the three IDENTICAL prompts would (correctly)
    # hit the cache and run the CoW recompute path, whose chunk-program
    # compile cold-marks request 2 — this test wants 3 warm full
    # prefills feeding the stats
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=32,
                      max_batch=2, prefix_cache=False)
    # precompiled engine: every request runs warm (compile detection is
    # per executable, so un-warmed second-signature compiles would
    # otherwise cold-mark trailing requests too)
    eng.precompile(8, 4)
    t0 = time.perf_counter()
    for i in range(3):
        eng.submit(np.arange(1, 9, dtype=np.int32), 4,
                   request_id=f"c{i}", submitted_at=t0)
    eng.run()
    s = ServingStats.from_events(eng.request_log).summary()
    assert s["requests"] == 3
    assert s["cold"] == 0
    pct = s["percentiles"]
    assert pct["ttft_s"]["count"] == 3
    assert pct["queue_delay_s"]["count"] == 3


# ---------------------------------------------------------------------------
# device tier: drain-and-reshard (elastic serving)
# ---------------------------------------------------------------------------


def test_drain_tapers_active_and_sheds_queued(lm, tmp_path):
    """drain(): admission closes, queued requests shed tenant-tagged,
    the in-flight lane finishes bit-exact through the normal loop, and
    late submits are rejected at the door."""
    from ddl_tpu.obs import EventWriter
    from ddl_tpu.obs.report import load_run
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    clients = _clients(3, np.random.default_rng(9), new_lo=3, new_hi=6)
    obs = EventWriter(tmp_path, "drain-test")
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=16,
                      max_batch=1, max_queue=4, obs=obs)
    for i, (cid, prompt, mn) in enumerate(clients):
        assert eng.submit(prompt, mn, request_id=cid,
                          tenant=f"t{i}") == "queued"
    eng.step()  # admits c0 into the single lane; c1/c2 stay queued
    assert len(eng.scheduler.active()) == 1

    counts = eng.drain("preempt")
    assert counts == {"shed": 2, "parked": 0}
    assert eng.draining and eng.drain_reason == "preempt"
    assert eng.outcomes["c1"] == "shed:drained"
    assert eng.outcomes["c2"] == "shed:drained"
    # a second call is a no-op (no double-shed, no duplicate event)
    assert eng.drain("preempt") == {"shed": 0, "parked": 0}
    # admission is closed: the late arrival sheds at the door
    assert eng.submit(clients[0][1], 3, request_id="late",
                      tenant="t9") == "rejected"
    assert eng.outcomes["late"] == "shed:draining"

    got = eng.run()  # taper: the in-flight lane finishes normally
    obs.close()
    assert sorted(got) == ["c0"]
    assert eng.outcomes["c0"] == "ok"
    want = _sequential_tokens(cfg, spec, params, clients[:1], seed=0)
    np.testing.assert_array_equal(got["c0"], want["c0"])
    assert eng.allocator.used_blocks == 0 and not eng.busy
    assert eng.stats["shed"] == 3

    events = load_run(tmp_path, "drain-test")
    drains = [e for e in events if e["kind"] == "serve_drain"]
    assert len(drains) == 1
    assert drains[0]["reason"] == "preempt"
    assert drains[0]["shed"] == 2 and drains[0]["active_lanes"] == 1
    sheds = {e["request_id"]: e for e in events
             if e["kind"] == "serve_shed" and e["reason"] == "drained"}
    assert sorted(sheds) == ["c1", "c2"]
    assert sheds["c1"]["tenant"] == "t1"  # shed stays SLO-attributable


def test_drain_park_hard_stops_lanes_with_partial_outputs(lm):
    """drain(park=True): the deadline the taper cannot meet — unfinished
    lanes park NOW with partial outputs recorded, blocks recycle, and
    the engine reports not-busy."""
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=32,
                      max_batch=2, max_steps_per_dispatch=1)
    eng.submit(np.arange(1, 9, dtype=np.int32), 12, request_id="a")
    eng.submit(np.arange(1, 6, dtype=np.int32), 12, request_id="b")
    # run both lanes into mid-decode (well short of 12 new tokens)
    for _ in range(3):
        eng.step()
    active = eng.scheduler.active()
    assert len(active) == 2
    assert all(0 < len(s.outputs) < 12 for s in active)

    counts = eng.drain("deadline", park=True)
    assert counts["parked"] == 2
    assert eng.outcomes["a"] == "parked:deadline"
    assert eng.outcomes["b"] == "parked:deadline"
    # partial outputs preserved so a resubmission can skip them
    assert 0 < len(eng.results["a"]) < 12
    # every block recycled, nothing left to do
    assert eng.allocator.used_blocks == 0
    assert not eng.busy and not eng.step()


@pytest.mark.parametrize(
    "kw", [dict(), dict(temperature=0.8, top_k=17)], ids=["greedy", "sampled"]
)
def test_parked_requests_resume_token_identical(lm, tmp_path, kw):
    """The serving half of an elastic grow epoch: requests parked
    mid-decode by drain(park=True) resume through resume_parked() and
    complete TOKEN-IDENTICAL to decodes that were never interrupted —
    greedy trivially, sampled because the parked rng carry replays the
    exact split sequence the uninterrupted lane would have drawn."""
    from ddl_tpu.obs import EventWriter
    from ddl_tpu.obs.report import load_run
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    clients = [
        ("a", np.arange(1, 9, dtype=np.int32), 12),
        ("b", np.arange(1, 6, dtype=np.int32), 12),
    ]
    obs = EventWriter(tmp_path, "resume-test")
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=32,
                      max_batch=2, max_steps_per_dispatch=1, obs=obs, **kw)
    for cid, prompt, mn in clients:
        eng.submit(prompt, mn, request_id=cid, rng_seed=5, tenant="t0")
    # run both lanes into mid-decode, then hard-stop for the restart
    for _ in range(4):
        eng.step()
    active = eng.scheduler.active()
    assert len(active) == 2
    assert all(0 < len(s.outputs) < 12 for s in active)
    progress = {s.request.id: len(s.outputs) for s in active}
    counts = eng.drain("scale_up", park=True)
    assert counts["parked"] == 2
    assert eng.allocator.used_blocks == 0

    # the grown pod's engine re-admits the parked work
    res = eng.resume_parked()
    assert res == {"resumed": 2, "rejected": 0}
    assert not eng.draining and eng.drain_reason is None
    got = eng.run()
    obs.close()

    want = _sequential_tokens(cfg, spec, params, clients, seed=5, **kw)
    assert sorted(got) == ["a", "b"]
    for cid, _, _mn in clients:
        np.testing.assert_array_equal(got[cid], want[cid])
        assert eng.outcomes[cid] == "ok"
    assert eng.allocator.used_blocks == 0 and not eng.busy

    # the resume is SLO-attributable: one serve_resume per request with
    # the park's progress and the remaining budget
    events = load_run(tmp_path, "resume-test")
    resumes = {e["request_id"]: e for e in events
               if e["kind"] == "serve_resume"}
    assert sorted(resumes) == ["a", "b"]
    for cid, n in progress.items():
        assert resumes[cid]["resumed_tokens"] == n
        assert resumes[cid]["remaining"] == 12 - n
        assert resumes[cid]["outcome"] != "rejected"
        assert resumes[cid]["tenant"] == "t0"


def test_preempt_guard_trips_drain_in_step(lm):
    """The supervisor-style preemption guard: step() polls it and flips
    the engine into drain without a direct drain() call."""
    from ddl_tpu.serve.engine import ServeEngine

    class Guard:
        requested = False

    cfg, params, spec = lm
    guard = Guard()
    eng = ServeEngine(cfg, params, spec, block_size=8, num_blocks=16,
                      max_batch=1, max_queue=4, guard=guard)
    c = _clients(2, np.random.default_rng(3), new_lo=3, new_hi=5)
    for cid, prompt, mn in c:
        eng.submit(prompt, mn, request_id=cid)
    eng.step()  # c0 admitted, guard quiet, c1 still queued
    assert not eng.draining
    guard.requested = True
    eng.step()
    assert eng.draining and eng.drain_reason == "preempt"
    assert eng.outcomes["c1"] == "shed:drained"
    got = eng.run()
    assert sorted(got) == ["c0"] and eng.outcomes["c0"] == "ok"
