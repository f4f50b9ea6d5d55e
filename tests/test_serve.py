"""Continuous-batching decode server: paged KV + engine + sampling.

The serving contracts under test:

- paged-cache decode is TOKEN-IDENTICAL to the dense `generate`
  (greedy, same seed) across block sizes and prefill chunkings —
  continuous batching changes when a request computes, never what;
- admission/eviction order is deterministic under a seeded trace;
- the block pool never leaks (allocated == freed after drain) and
  admission blocks (head-of-line) on pool exhaustion;
- the server survives a mid-stream request cancel;
- runtime-parameter sampling (`serve.sampling`) reproduces the static
  sampler exactly for equal settings.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_dist import models, serve


@pytest.fixture(scope="module")
def lm():
    return models.TransformerLM(vocab=64, dim=32, depth=2, heads=4,
                                max_seq=48)


@pytest.fixture(scope="module")
def lm_params(lm):
    params, _ = lm.init(jax.random.key(7))
    return params


def _cfg(**kw):
    base = dict(max_batch=4, block_size=8, num_blocks=64, max_seq=32,
                prefill_chunk=8)
    base.update(kw)
    return serve.ServeConfig(**base)


class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = serve.BlockAllocator(8)
        got = a.alloc(3)
        assert got == [0, 1, 2] and a.used == 3
        a.free(got)
        assert a.used == 0 and a.available == 8

    def test_exhaustion_returns_none(self):
        a = serve.BlockAllocator(4)
        assert a.alloc(5) is None
        first = a.alloc(4)
        assert a.alloc(1) is None
        a.free(first[:1])
        assert a.alloc(1) is not None

    def test_double_free_raises(self):
        a = serve.BlockAllocator(4)
        blocks = a.alloc(2)
        a.free(blocks)
        with pytest.raises(ValueError, match="unallocated"):
            a.free(blocks[:1])

    def test_high_water(self):
        a = serve.BlockAllocator(8)
        x = a.alloc(5)
        a.free(x)
        a.alloc(2)
        assert a.high_water == 5


class TestPagedParity:
    """Paged greedy decode bit-matches dense `generate`."""

    @pytest.mark.parametrize("block_size", [4, 8, 16])
    def test_greedy_matches_dense_across_block_sizes(
        self, lm, lm_params, block_size
    ):
        prompts = models.synthetic_tokens(4, 6, 64, seed=3)
        dense = np.asarray(lm.generate(lm_params, prompts, 10, cache_len=32))
        eng = serve.ServeEngine(lm, lm_params, _cfg(block_size=block_size))
        rids = [eng.submit(np.asarray(prompts[i]), 10) for i in range(4)]
        res = eng.run_until_drained()
        got = np.stack([res[r].tokens for r in rids])
        np.testing.assert_array_equal(got, dense)

    @pytest.mark.parametrize("chunk", [3, 5, 16])
    def test_chunked_prefill_matches_dense(self, lm, lm_params, chunk):
        """Prompt ingestion split into chunks of any size reproduces
        the one-shot prefill's continuation."""
        prompts = models.synthetic_tokens(3, 11, 64, seed=5)
        dense = np.asarray(lm.generate(lm_params, prompts, 8, cache_len=32))
        eng = serve.ServeEngine(
            lm, lm_params, _cfg(prefill_chunk=chunk)
        )
        rids = [eng.submit(np.asarray(prompts[i]), 8) for i in range(3)]
        res = eng.run_until_drained()
        got = np.stack([res[r].tokens for r in rids])
        np.testing.assert_array_equal(got, dense)

    def test_greedy_matches_with_mixed_sampling_neighbors(
        self, lm, lm_params
    ):
        """A greedy request sharing the batch with sampled requests
        still bit-matches the dense decode (per-slot sampling params
        cannot leak across slots)."""
        prompts = models.synthetic_tokens(3, 6, 64, seed=9)
        dense = np.asarray(lm.generate(lm_params, prompts, 10, cache_len=32))
        eng = serve.ServeEngine(lm, lm_params, _cfg())
        rid = eng.submit(np.asarray(prompts[0]), 10)
        eng.submit(
            np.asarray(prompts[1]), 10,
            sampling=serve.SamplingParams(temperature=0.9, top_k=8, seed=4),
        )
        eng.submit(
            np.asarray(prompts[2]), 10,
            sampling=serve.SamplingParams(temperature=1.0, top_p=0.9,
                                          seed=5),
        )
        res = eng.run_until_drained()
        np.testing.assert_array_equal(res[rid].tokens, dense[0])

    def test_gqa_rope_window_variants(self):
        """GQA caches, rope positions, and the sliding-window band all
        ride the paged path unchanged."""
        prompts = models.synthetic_tokens(2, 6, 64, seed=2)
        for kw in (
            {"kv_heads": 2},
            {"pos_embedding": "rope"},
            {"sliding_window": 8},
        ):
            lm_v = models.TransformerLM(
                vocab=64, dim=32, depth=2, heads=4, max_seq=48, **kw
            )
            params, _ = lm_v.init(jax.random.key(1))
            dense = np.asarray(
                lm_v.generate(params, prompts, 8, cache_len=32)
            )
            eng = serve.ServeEngine(lm_v, params, _cfg(max_batch=2))
            rids = [eng.submit(np.asarray(prompts[i]), 8) for i in range(2)]
            res = eng.run_until_drained()
            got = np.stack([res[r].tokens for r in rids])
            np.testing.assert_array_equal(got, dense, err_msg=str(kw))

    def test_staggered_admission_matches_dense(self, lm, lm_params):
        """Requests admitted into slots mid-flight (continuous
        batching's whole point) still decode exactly like the dense
        path — slot reuse cannot leak stale KV into a new request."""
        prompts = models.synthetic_tokens(6, 6, 64, seed=11)
        dense = np.asarray(lm.generate(lm_params, prompts, 8, cache_len=32))
        eng = serve.ServeEngine(lm, lm_params, _cfg(max_batch=2))
        rids = [eng.submit(np.asarray(prompts[i]), 8) for i in range(6)]
        res = eng.run_until_drained()
        got = np.stack([res[r].tokens for r in rids])
        np.testing.assert_array_equal(got, dense)


class TestPoolLayout:
    """The pool is ``(num_blocks + 1, block_size, kv_heads * head_dim)``:
    a token's k/v is ONE contiguous row at ``[blk, off]``, heads major
    within it (docs/serving.md: the shape the device's own layout agrees
    with, so no program relayouts the pool)."""

    @pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
    def test_rows_land_at_blk_off_and_masked_writes_in_scratch(self, kv_heads):
        from tpu_dist.parallel import per_device_bytes

        bs, nblk, depth, hd = 4, 6, 2, 8
        lm = models.TransformerLM(vocab=64, dim=32, depth=depth, heads=4,
                                  max_seq=16, kv_heads=kv_heads)
        params, _ = lm.init(jax.random.key(3))
        cache = serve.init_paged_cache(lm, nblk, bs)
        assert cache[0]["k"].shape == (nblk + 1, bs, kv_heads * hd)
        assert per_device_bytes(cache) == (
            2 * depth * (nblk + 1) * bs * kv_heads * hd * 4)

        # slot 0 writes positions 2..5 through blocks (5, 1); slot 1 is
        # masked from its third token on (a padded prefill row)
        tokens = jnp.asarray([[3, 9, 27, 17], [5, 25, 61, 49]], jnp.int32)
        positions = jnp.asarray([[2, 3, 4, 5], [0, 1, 2, 3]], jnp.int32)
        tables = jnp.asarray([[5, 1, nblk], [2, nblk, nblk]], jnp.int32)
        mask = jnp.asarray([[True] * 4, [True, True, False, False]])
        _, new = serve.paged_apply_cached(
            lm, params, tokens, cache, tables, positions, mask, bs)

        # the first block's k/v, computed apart from the paged path
        h = params["embed"]["table"][tokens] + params["pos"][0][positions]
        blk0, pb0 = lm.blocks[0], params["blocks"][0]
        x1, _ = blk0.ln1.apply(pb0["ln1"], {}, h)
        _, k, v = blk0.attn._project(pb0["attn"], x1)  # (S, kv_heads, s, hd)
        for side, t in (("k", np.asarray(k)), ("v", np.asarray(v))):
            want = np.zeros((nblk, bs, kv_heads, hd), np.float32)
            for slot, tok in np.argwhere(np.asarray(mask)):
                pos = int(positions[slot, tok])
                want[int(tables[slot, pos // bs]), pos % bs] = t[slot, :, tok]
            got = np.asarray(new[0][side]).reshape(nblk + 1, bs, kv_heads, hd)
            np.testing.assert_allclose(got[:nblk], want, rtol=1e-6, atol=1e-6)
            # the two masked tokens (positions 2, 3) went to scratch alone
            assert np.abs(got[nblk, 2:]).sum() > 0
            assert not got[nblk, :2].any()


class TestServingProtocol:
    """The engine asks the model for `init_serve_cache` / `apply_paged`
    and knows nothing of a block's inside (docs/serving.md); the
    transformer offers the two over the paged pool, keeps no per-slot
    state and counts nothing itself.  `tests/test_hybrid_lm.py` serves a
    model that does both through the same engine."""

    def test_the_transformer_delegates_to_the_paged_pool(self, lm, lm_params):
        bs, nblk = 4, 6
        cache = lm.init_serve_cache(3, nblk, bs)
        assert set(cache) == {"kv", "state"} and not jax.tree.leaves(cache["state"])
        tokens = jnp.asarray([[3, 9, 27, 17], [5, 25, 61, 49]], jnp.int32)
        positions = jnp.asarray([[2, 3, 4, 5], [0, 1, 2, 3]], jnp.int32)
        tables = jnp.asarray([[5, 1, nblk], [2, nblk, nblk]], jnp.int32)
        mask = jnp.asarray([[True] * 4, [True, True, False, False]])
        want, want_kv = serve.paged_apply_cached(
            lm, lm_params, tokens, serve.init_paged_cache(lm, nblk, bs),
            tables, positions, mask, bs)
        got, new, counters = lm.apply_paged(
            lm_params, tokens, cache, tables, positions, mask,
            jnp.asarray([1, 0]), bs)
        assert counters is None and lm.serve_counters == ()
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(new["kv"]), jax.tree.leaves(want_kv)))

    def test_an_engine_without_a_state_accounts_none(self, lm, lm_params):
        eng = serve.ServeEngine(lm, lm_params, _cfg(bytes_limit=1 << 40))
        bd = eng.memory_breakdown()
        assert eng.state_bytes == bd["state_bytes"] == 0
        assert bd["activation_headroom_bytes"] == (
            (1 << 40) - eng.weights_bytes - eng.kv_pool_bytes)
        assert set(eng.analysis_programs()) == {"serve_decode", "serve_prefill"}
        # a prefill row carries its slot: one more column of the packed ints
        p_ints = eng.analysis_programs()["serve_prefill"][1][2]
        assert p_ints.shape == (eng.cfg.prefill_batch,
                                eng.cfg.prefill_chunk + eng.blocks_per_seq + 5)
        eng.submit(np.arange(1, 12, dtype=np.int32), 4)
        eng.run_until_drained()
        from tpu_dist.observe import spans

        done = [s for s in spans.recent() if s.name == "request.prefill"][-1]
        assert done.attrs["state_reset"] is False


class TestEngineScheduling:
    def test_deterministic_under_seeded_trace(self, lm, lm_params):
        """Same trace, same engine config -> identical admission /
        eviction audit and identical tokens, run to run."""

        def run():
            eng = serve.ServeEngine(lm, lm_params, _cfg(max_batch=2))
            rng = np.random.default_rng(0)
            for i in range(6):
                plen = int(rng.integers(2, 7))
                steps = int(rng.integers(2, 9))
                prompt = models.synthetic_tokens(1, plen, 64, seed=i)[0]
                temp = 0.0 if i % 2 else 0.8
                eng.submit(
                    np.asarray(prompt), steps,
                    sampling=serve.SamplingParams(
                        temperature=temp, top_k=8, seed=i
                    ),
                )
            res = eng.run_until_drained()
            toks = {r: res[r].tokens.tolist() for r in res}
            return eng.audit, toks

        audit1, toks1 = run()
        audit2, toks2 = run()
        assert audit1 == audit2
        assert toks1 == toks2
        kinds = [a[0] for a in audit1]
        assert "admit" in kinds and "finish" in kinds

    def test_pool_never_leaks_under_churn(self, lm, lm_params):
        """allocated == freed after drain, across many admit/evict
        cycles with mixed lengths (slots and blocks reused)."""
        eng = serve.ServeEngine(
            lm, lm_params, _cfg(max_batch=2, num_blocks=12)
        )
        rng = np.random.default_rng(1)
        for i in range(10):
            plen = int(rng.integers(1, 8))
            eng.submit(
                models.synthetic_tokens(1, plen, 64, seed=i)[0],
                int(rng.integers(1, 10)),
            )
        res = eng.run_until_drained()
        assert len(res) == 10
        assert eng.allocator.used == 0
        assert eng.allocator.available == 12
        assert eng.allocator.high_water > 0

    def test_admission_blocks_on_pool_exhaustion(self, lm, lm_params):
        """num_blocks too small for two requests: the second stays
        queued until the first frees its blocks (head-of-line, FIFO)."""
        # each request needs ceil((6+10)/8) = 2 blocks; pool holds 2
        eng = serve.ServeEngine(
            lm, lm_params, _cfg(max_batch=4, num_blocks=2)
        )
        p = models.synthetic_tokens(2, 6, 64, seed=0)
        r0 = eng.submit(np.asarray(p[0]), 10)
        r1 = eng.submit(np.asarray(p[1]), 10)
        eng.step()
        admits = [a for a in eng.audit if a[0] == "admit"]
        assert [a[1] for a in admits] == [r0]  # r1 waits on the pool
        assert len(eng.queue) == 1
        res = eng.run_until_drained()
        admits = [a for a in eng.audit if a[0] == "admit"]
        assert [a[1] for a in admits] == [r0, r1]
        assert res[r1].tokens.size == 10
        assert eng.allocator.used == 0

    def test_oversized_request_rejected(self, lm, lm_params):
        eng = serve.ServeEngine(lm, lm_params, _cfg())
        with pytest.raises(ValueError, match="max_seq"):
            eng.submit(np.zeros(30, np.int32), 10)  # 40 > 32
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros(0, np.int32), 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.zeros(4, np.int32), 0)

    def test_pool_impossible_request_rejected_not_livelocked(
        self, lm, lm_params
    ):
        """A request needing more blocks than the whole pool must be
        rejected at submit — queueing it would livelock the FIFO head
        forever (no eviction can ever free enough)."""
        eng = serve.ServeEngine(
            lm, lm_params, _cfg(max_batch=4, num_blocks=2)
        )
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit(np.zeros(10, np.int32), 20)  # needs 4 > 2
        assert not eng.pending  # nothing queued

    def test_warmup_compiles_both_decode_paths_silently(
        self, lm, lm_params, tmp_path, monkeypatch
    ):
        """warmup() must trace the greedy AND sampled decode programs
        (the first tempered request must not pay a compile inside the
        serving loop) without emitting any telemetry — no lifecycle
        events on disk, no TTFT/TPOT histogram samples."""
        from tpu_dist.observe import events as ev_mod
        from tpu_dist.observe.registry import REGISTRY

        out = str(tmp_path / "warmup_events")
        monkeypatch.setenv("TPU_DIST_TELEMETRY", out)
        ttft = REGISTRY.histogram("tpu_dist_serve_ttft_seconds")
        tpot = REGISTRY.histogram("tpu_dist_serve_tpot_seconds")
        before = (ttft.count(), tpot.count())
        eng = serve.ServeEngine(lm, lm_params, _cfg())
        eng.warmup()
        assert eng._decode_fn_greedy._cache_size() == 1
        assert eng._decode_fn._cache_size() == 1
        assert (ttft.count(), tpot.count()) == before
        assert not eng.results and not eng.audit
        files = ev_mod.event_files(out)
        recs = ev_mod.read_events(out) if files else []
        assert not recs, recs[:3]
        eng.events.close()

    def test_stop_token_finishes_early(self, lm, lm_params):
        prompt = models.synthetic_tokens(1, 5, 64, seed=3)[0]
        free = np.asarray(
            lm.generate(lm_params, prompt[None], 12, cache_len=32)
        )[0]
        stop = int(free[3])
        first = int(np.nonzero(free == stop)[0][0])
        eng = serve.ServeEngine(lm, lm_params, _cfg())
        rid = eng.submit(np.asarray(prompt), 12, stop_token=stop)
        res = eng.run_until_drained()
        assert res[rid].finish_reason == "stop"
        assert res[rid].tokens[-1] == stop
        assert res[rid].tokens.size == first + 1  # trimmed at first stop
        np.testing.assert_array_equal(res[rid].tokens, free[: first + 1])

    def test_cancel_mid_stream(self, lm, lm_params):
        """Cancelling an in-flight request frees its slot/blocks and
        the engine keeps serving everyone else."""
        prompts = models.synthetic_tokens(3, 5, 64, seed=6)
        dense = np.asarray(lm.generate(lm_params, prompts, 10, cache_len=32))
        eng = serve.ServeEngine(lm, lm_params, _cfg(max_batch=2))
        victim = eng.submit(np.asarray(prompts[0]), 20)
        keep = eng.submit(np.asarray(prompts[1]), 10)
        for _ in range(4):
            eng.step()
        assert eng.cancel(victim)
        late = eng.submit(np.asarray(prompts[2]), 10)
        res = eng.run_until_drained()
        assert res[victim].finish_reason == "cancelled"
        assert 0 < res[victim].emitted < 20
        # the cancelled prefix matches the dense decode
        np.testing.assert_array_equal(
            res[victim].tokens, dense[0][: res[victim].emitted]
        )
        np.testing.assert_array_equal(res[keep].tokens, dense[1])
        np.testing.assert_array_equal(res[late].tokens, dense[2])
        assert eng.allocator.used == 0

    def test_cancel_queued_and_unknown(self, lm, lm_params):
        eng = serve.ServeEngine(
            lm, lm_params, _cfg(max_batch=1)
        )
        r0 = eng.submit(models.synthetic_tokens(1, 4, 64)[0], 4)
        r1 = eng.submit(models.synthetic_tokens(1, 4, 64)[0], 4)
        assert eng.cancel(r1)  # still queued
        assert not eng.cancel(999)
        res = eng.run_until_drained()
        assert res[r1].finish_reason == "cancelled"
        assert res[r1].emitted == 0
        assert res[r0].emitted == 4

    def test_sampled_stream_is_scheduling_independent(self, lm, lm_params):
        """A sampled request's tokens depend only on (seed, token
        index) — not on which slot it lands in or who shares the
        batch."""
        prompts = models.synthetic_tokens(3, 6, 64, seed=8)
        sp = serve.SamplingParams(temperature=0.9, top_k=8, seed=5)
        eng1 = serve.ServeEngine(lm, lm_params, _cfg())
        r1 = eng1.submit(np.asarray(prompts[1]), 10, sampling=sp)
        eng1.submit(np.asarray(prompts[0]), 10)
        res1 = eng1.run_until_drained()
        eng2 = serve.ServeEngine(lm, lm_params, _cfg())
        eng2.submit(np.asarray(prompts[2]), 3)
        eng2.submit(np.asarray(prompts[0]), 7)
        r2 = eng2.submit(np.asarray(prompts[1]), 10, sampling=sp)
        res2 = eng2.run_until_drained()
        np.testing.assert_array_equal(res1[r1].tokens, res2[r2].tokens)

    def test_latency_fields_with_fake_clock(self, lm, lm_params):
        t = [0.0]

        def clock():
            t[0] += 0.5
            return t[0]

        eng = serve.ServeEngine(lm, lm_params, _cfg(), now=clock)
        rid = eng.submit(models.synthetic_tokens(1, 4, 64)[0], 5)
        res = eng.run_until_drained()[rid]
        assert res.ttft is not None and res.ttft > 0
        assert res.tpot_mean is not None and res.tpot_mean > 0
        assert res.finish_time > res.first_token_time
        assert len(res.token_times) == res.emitted == 5


class TestServeTelemetry:
    def test_events_validate_and_metrics_publish(
        self, lm, lm_params, tmp_path, monkeypatch
    ):
        from tpu_dist.observe import events as ev_mod
        from tpu_dist.observe.registry import REGISTRY

        out = str(tmp_path / "serve_events")
        monkeypatch.setenv("TPU_DIST_TELEMETRY", out)
        eng = serve.ServeEngine(
            lm, lm_params, _cfg(max_batch=2, decode_event_every=1)
        )
        prompts = models.synthetic_tokens(3, 5, 64, seed=4)
        for i in range(3):
            eng.submit(np.asarray(prompts[i]), 6)
        eng.run_until_drained()
        eng.events.close()

        n, errors = ev_mod.validate_dir(out)
        assert not errors, errors[:5]
        kinds = {}
        for rec in ev_mod.read_events(out):
            kinds.setdefault(rec["event"], []).append(rec)
        for k in ("request_admit", "prefill", "decode_step",
                  "request_finish"):
            assert k in kinds, (k, sorted(kinds))
        fin = kinds["request_finish"]
        assert len(fin) == 3
        assert all(f["emitted"] == 6 for f in fin)
        assert all(f["finish_reason"] == "length" for f in fin)
        d = kinds["decode_step"][0]
        assert set(
            ("step", "occupancy", "queue_depth", "kv_blocks_used",
             "kv_block_utilization")
        ) <= set(d)

        assert REGISTRY.gauge("tpu_dist_serve_kv_blocks_used").value() == 0
        assert (
            REGISTRY.histogram("tpu_dist_serve_ttft_seconds").count() >= 3
        )
        assert (
            REGISTRY.histogram("tpu_dist_serve_tpot_seconds").count() > 0
        )

    def test_tpu_top_renders_serve_line(
        self, lm, lm_params, tmp_path, monkeypatch
    ):
        import os
        import sys

        sys.path.insert(
            0,
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "tools"),
        )
        import tpu_top

        out = str(tmp_path / "serve_top")
        monkeypatch.setenv("TPU_DIST_TELEMETRY", out)
        eng = serve.ServeEngine(
            lm, lm_params, _cfg(decode_event_every=1)
        )
        eng.submit(models.synthetic_tokens(1, 4, 64)[0], 4)
        eng.run_until_drained()
        eng.events.close()
        frame = tpu_top.render(tpu_top.collect(out))
        assert "serve" in frame and "occupancy" in frame
        assert "queue" in frame and "kv-blocks" in frame


class TestLMServer:
    def test_server_from_artifact_round_trip(self, lm, lm_params, tmp_path):
        from tpu_dist import export

        path = tmp_path / "weights.npz"
        export.save_params(lm_params, path)
        srv = serve.LMServer.from_artifact(lm, path, _cfg())
        prompt = models.synthetic_tokens(1, 5, 64, seed=1)
        rid = srv.submit(np.asarray(prompt[0]), 8)
        res = srv.run_until_drained()
        dense = np.asarray(lm.generate(lm_params, prompt, 8, cache_len=32))
        np.testing.assert_array_equal(res[rid].tokens, dense[0])
        assert srv.result(rid) is res[rid]
        assert not srv.pending


class TestRuntimeSampling:
    """`serve.sampling`: traced-parameter sampling == the static
    sampler for equal settings."""

    @pytest.mark.parametrize(
        "kw",
        [
            dict(temperature=0.0, top_k=None, top_p=None),
            dict(temperature=0.8, top_k=None, top_p=None),
            dict(temperature=0.8, top_k=8, top_p=None),
            dict(temperature=1.0, top_k=None, top_p=0.9),
            dict(temperature=0.7, top_k=16, top_p=0.8),
        ],
    )
    def test_generate_runtime_matches_static_generate(
        self, lm, lm_params, kw
    ):
        prompt = models.synthetic_tokens(2, 5, 64, seed=3)
        key = jax.random.key(11)
        want = np.asarray(lm.generate(lm_params, prompt, 10, key=key, **kw))
        got = np.asarray(
            serve.generate_runtime(
                lm, lm_params, prompt, 10, key=key,
                temperature=kw["temperature"], top_k=kw["top_k"],
                top_p=kw["top_p"],
            )
        )
        np.testing.assert_array_equal(got, want)

    def test_one_program_many_configs(self, lm, lm_params):
        """The whole point: one jitted program serves every sampling
        config (params are traced, not baked)."""
        import functools

        prompt = models.synthetic_tokens(1, 4, 64, seed=2)
        f = jax.jit(
            functools.partial(serve.generate_runtime, lm, lm_params,
                              steps=8)
        )
        greedy = f(prompt=prompt, key=jax.random.key(0),
                   temperature=0.0, top_k=0, top_p=1.0)
        sampled = f(prompt=prompt, key=jax.random.key(0),
                    temperature=0.9, top_k=8, top_p=0.95)
        np.testing.assert_array_equal(
            np.asarray(greedy), np.asarray(lm.generate(lm_params, prompt, 8))
        )
        assert not np.array_equal(np.asarray(greedy), np.asarray(sampled))

    def test_sample_slots_greedy_is_argmax(self):
        logits = jax.random.normal(jax.random.key(0), (4, 16))
        keys = serve.slot_keys(
            jnp.arange(4, dtype=jnp.int32), jnp.zeros(4, jnp.int32)
        )
        toks = serve.sample_slots(
            logits, keys, jnp.zeros(4), jnp.zeros(4, jnp.int32),
            jnp.ones(4),
        )
        np.testing.assert_array_equal(
            np.asarray(toks), np.asarray(jnp.argmax(logits, -1))
        )

    def test_cache_overflow_raises(self, lm, lm_params):
        prompt = models.synthetic_tokens(1, 40, 64, seed=0)
        with pytest.raises(ValueError, match="exceeds cache length"):
            serve.generate_runtime(lm, lm_params, prompt, 20)


PHASES = {
    "engine.admit", "engine.decode_dispatch", "engine.prefill_dispatch",
    "engine.decode_wait", "engine.decode_apply", "engine.prefill_wait",
    "engine.prefill_apply", "engine.publish",
}


def _serve_traced(lm, lm_params, lengths, *, max_new=5, **cfg):
    """Drain an engine over prompts of ``lengths``; returns (engine,
    request ids, the spans it left in `observe.spans`' ring)."""
    import time

    from tpu_dist.observe import spans

    eng = serve.ServeEngine(lm, lm_params, _cfg(**cfg))
    t0 = time.perf_counter()  # after its construction's own spans (`engine.init`)
    rids = [
        eng.submit(models.synthetic_tokens(1, n, 64, seed=i)[0], max_new)
        for i, n in enumerate(lengths)
    ]
    eng.run_until_drained()
    return eng, rids, spans.recent(since=t0)


class TestEngineSpans:
    """The engine names its own work: one `engine.step` span a call with
    its phases as children, three spans a request, counters on both."""

    def test_every_phase_span_lies_inside_its_engine_step(self, lm, lm_params):
        eng, _, got = _serve_traced(lm, lm_params, [3, 11, 20, 6, 9])
        steps = {s.id: s for s in got if s.name == "engine.step"}
        assert len(steps) == eng.step_count
        assert [s.attrs["step"] for s in steps.values()] == list(range(eng.step_count))
        phases = [s for s in got if s.name.startswith("engine.") and s.name != "engine.step"]
        assert {s.name for s in phases} == PHASES
        for s in phases:
            step = steps[s.parent]
            assert step.start <= s.start <= s.end <= step.end
        for step in steps.values():
            assert step.parent is None
            assert set(step.attrs) == {"step", "occupancy", "queued", "admitted", "blocked"}
            mine = [s.name for s in phases if s.parent == step.id]
            assert mine[0] == "engine.admit" and mine[-1] == "engine.publish"
            assert len(mine) == len(set(mine))  # each phase once a step at most
            # only the readbacks wait for the device; a step's decode
            # dispatch comes before its decode wait, which reads the step
            # the call BEFORE launched (a call with nothing left to launch
            # still reads: the pipeline empties itself)
            if "engine.decode_wait" in mine:
                if "engine.decode_dispatch" in mine:
                    assert mine.index("engine.decode_dispatch") < mine.index("engine.decode_wait")
                assert mine.index("engine.decode_wait") + 1 == mine.index("engine.decode_apply")
            if "engine.prefill_wait" in mine:
                assert mine.index("engine.prefill_wait") + 1 == mine.index("engine.prefill_apply")
        assert sum(s.attrs["admitted"] for s in steps.values()) == 5

    def test_each_finished_request_has_three_spans_and_one_id(self, lm, lm_params):
        eng, rids, got = _serve_traced(lm, lm_params, [3, 11, 20, 6, 9, 4])
        for rid in rids:
            mine = {s.name: s for s in got if s.attrs.get("request_id") == rid}
            assert set(mine) == {"request.queued", "request.prefill", "request.decode"}
            q, p, d = (mine[f"request.{k}"] for k in ("queued", "prefill", "decode"))
            res = eng.results[rid]
            assert q.start == res.arrival_time and q.end == p.start == res.admit_time
            assert p.end == d.start == res.first_token_time and d.end == res.finish_time
            assert d.attrs["emitted"] == res.emitted == 5
            assert d.attrs["finish_reason"] == res.finish_reason == "length"
            assert len({q.id, p.id, d.id}) == 3 and q.parent is None
        assert sum(s.name == "request.queued" for s in got) == len(rids)

    @pytest.mark.parametrize("chunk", [4, 8, 32])
    def test_prefill_rounds_count_the_prompts_tokens(self, lm, lm_params, chunk):
        lengths = [3, 11, 20, 6, 9]
        eng, _, got = _serve_traced(lm, lm_params, lengths, prefill_chunk=chunk)
        rounds = [s for s in got if s.name == "engine.prefill_dispatch"]
        assert sum(s.attrs["real_tokens"] for s in rounds) == sum(lengths)
        assert all(s.attrs["chunk"] == chunk for s in rounds)
        assert all(1 <= s.attrs["rows"] <= eng.cfg.prefill_batch for s in rounds)
        want_rows = sum(-(-n // chunk) for n in lengths)  # a row a chunk a request
        assert sum(s.attrs["rows"] for s in rounds) == want_rows
        assert len(rounds) == eng.steps_with_prefill

    @pytest.mark.parametrize("cause,cfg", [
        # each request needs ceil((6+10)/8) = 2 blocks
        ("blocks", dict(max_batch=4, num_blocks=2)),
        ("slots", dict(max_batch=1, num_blocks=64)),
    ])
    def test_blocked_names_what_the_queues_head_waits_for(self, lm, lm_params, cause, cfg):
        eng, rids, got = _serve_traced(lm, lm_params, [6, 6], max_new=10, **cfg)
        steps = [s for s in got if s.name == "engine.step"]
        assert steps[0].attrs["admitted"] == 1 and steps[0].attrs["queued"] == 1
        said = [s.attrs["blocked"] for s in steps]
        assert said[0] == cause and set(said) == {cause, ""}
        # blocked for as long as the second request stayed queued, not after
        waiting = [s for s in steps if s.attrs["queued"] == 1]
        assert [s.attrs["blocked"] for s in waiting] == [cause] * len(waiting)
        assert said[len(waiting):] == [""] * (len(steps) - len(waiting))
        q = [s for s in got if s.name == "request.queued"]
        assert q[1].ms > q[0].ms and eng.results[rids[1]].emitted == 10

    def test_repacks_and_decode_steps_are_counted(self, lm, lm_params):
        from tpu_dist.observe.registry import REGISTRY

        names = ["admitted", "prefill_rows", "prefill_real_tokens",
                 "prefill_padded_tokens", "state_repacks", "decode_steps"]
        counter = lambda n: REGISTRY.counter(f"tpu_dist_serve_{n}_total")  # noqa: E731
        before = {n: counter(n).value() for n in names}
        blocked0 = counter("blocked_steps").value(cause="slots")
        eng, _, got = _serve_traced(lm, lm_params, [5, 7, 9], max_batch=2)
        delta = {n: counter(n).value() - before[n] for n in names}
        decodes = [s for s in got if s.name == "engine.decode_dispatch"]
        rounds = [s for s in got if s.name == "engine.prefill_dispatch"]
        assert delta["admitted"] == 3 and delta["prefill_real_tokens"] == 21
        assert delta["decode_steps"] == len(decodes) == eng.steps_with_decode
        assert delta["state_repacks"] == sum(s.attrs["repacked"] for s in decodes) >= 2
        assert delta["prefill_rows"] == sum(s.attrs["rows"] for s in rounds)
        assert delta["prefill_padded_tokens"] == delta["prefill_rows"] * 8
        blocked = sum(s.attrs["blocked"] == "slots" for s in got if s.name == "engine.step")
        assert counter("blocked_steps").value(cause="slots") - blocked0 == blocked > 0
        assert "tpu_dist_serve_blocked_steps_total" in REGISTRY.render()

    def test_warmup_leaves_no_count_and_a_cancel_in_the_queue_no_span(self, lm, lm_params):
        import time

        from tpu_dist.observe import spans
        from tpu_dist.observe.registry import REGISTRY

        admitted = REGISTRY.counter("tpu_dist_serve_admitted_total")
        before = admitted.value()
        eng = serve.ServeEngine(lm, lm_params, _cfg())
        eng.warmup()
        assert admitted.value() == before and not eng.audit
        t0 = time.perf_counter()
        rid = eng.submit(models.synthetic_tokens(1, 4, 64, seed=0)[0], 3)
        assert eng.cancel(rid) and eng.results[rid].admit_time is None
        assert not [s for s in spans.recent(since=t0) if s.name.startswith("request.")]

    def test_the_telemetry_file_holds_the_servers_spans(self, lm, lm_params, tmp_path,
                                                        monkeypatch):
        """Under ``TPU_DIST_TELEMETRY`` a server's Chrome-trace file (the
        documented export, `merge_traces`' input) holds the engine's phases
        on the engine's thread and each request's life on a lane of its own,
        all stamped by the spans' clock through the real front-end."""
        import json

        from tpu_dist.observe import events, spans

        monkeypatch.setenv(events.ENV_DIR, str(tmp_path))
        server = serve.LMServer(lm, lm_params, _cfg())
        assert server.engine._now is spans.time.perf_counter
        rids = [server.submit(models.synthetic_tokens(1, n, 64, seed=n)[0], 4) for n in (5, 9, 14)]
        server.run_until_drained()
        rec = spans.from_env()
        assert not (tmp_path / "spans_rank0.trace.json").exists()
        spans.flush_all()  # what interpreter exit and the crash paths call
        doc = json.load(open(rec.path))
        evs = doc["traceEvents"]
        steps = [e for e in evs if e["name"] == "engine.step"]
        assert len(steps) == server.engine.step_count and doc["otherData"]["complete"]
        assert PHASES <= {e["name"] for e in evs}
        assert len({e["tid"] for e in evs if e["name"].startswith("engine.")}) == 1
        for rid in rids:
            lane = sorted((e for e in evs if e["tid"] == rid), key=lambda e: e["ts"])
            assert [e["name"] for e in lane] == ["request.queued", "request.prefill",
                                                 "request.decode"]
            res = server.result(rid)
            # the lane reads as the request's TTFT and its decode time
            assert lane[1]["ts"] + lane[1]["dur"] == pytest.approx(lane[2]["ts"])
            assert (lane[2]["ts"] - lane[0]["ts"]) / 1e6 == pytest.approx(res.ttft, abs=1e-6)
            assert lane[2]["args"]["emitted"] == 4
            # a request's prefill lies under the steps that ran its rounds
            inside = [e for e in steps if e["ts"] < lane[1]["ts"] + lane[1]["dur"]
                      and e["ts"] + e["dur"] > lane[1]["ts"]]
            assert inside
        merged = spans.merge_traces([rec.path])
        assert {"request.decode", "engine.decode_wait"} <= {e["name"] for e in merged["traceEvents"]}

    def test_the_audit_is_bounded(self, lm, lm_params):
        eng = serve.ServeEngine(lm, lm_params, _cfg())
        assert eng.audit.maxlen >= 2 * 10_000  # two tuples a request


# --------------------------------------------- the look-ahead of one step


def _family_lm(name):
    """A family's rehearsal-size model through its own builder (float32)."""
    import importlib
    import json
    from pathlib import Path

    family = importlib.import_module(f"chipbench.families.{name}")
    config = {"granitemoehybrid": "granite-4.0-h-small", "afmoe": "Trinity-Large-Preview"}[name]
    published = json.loads(
        (Path(__file__).resolve().parents[1] / f"chipbench/configs/{config}.json").read_text())
    model = family.make_lm(dict(published, **family.tiny(published)), jax.random.key(7), "float32")
    return model, model.init()[0], 512


@pytest.fixture(scope="module")
def served_models():
    """{kind: (model, params, vocab)}: paged K/V alone, a recurrent state a
    slot beside it (Mamba-2 layers), per-slot K/V rings beside it."""
    roomy = models.TransformerLM(vocab=64, dim=32, depth=2, heads=4, max_seq=96)
    made = {"paged": (roomy, roomy.init(jax.random.key(7))[0], 64)}

    def get(kind):
        if kind not in made:
            made[kind] = _family_lm({"recurrent": "granitemoehybrid", "ring": "afmoe"}[kind])
        return made[kind]

    return get


def _alone(model, params, cfg, prompt, n, sampling=None):
    """The recorded stream: the request served alone to its length by a
    fresh engine, so no slot is reused and nothing stops early."""
    eng = serve.ServeEngine(model, params, cfg)
    rid = eng.submit(prompt, n, sampling=sampling)
    return eng.run_until_drained()[rid].tokens


def _histograms():
    from tpu_dist.observe.registry import REGISTRY

    return (REGISTRY.histogram("tpu_dist_serve_ttft_seconds").count(),
            REGISTRY.histogram("tpu_dist_serve_tpot_seconds").count())


class TestLookAhead:
    """`ServeEngine.step` launches decode step n before it reads step n-1.
    The tokens are the ones the engine served before; a stop token or a
    cancel finds one step in flight, whose token for that slot is never
    seen; a finish by length is counted ahead and overruns nothing."""

    SAMPLED = dict(temperature=0.9, top_k=8, top_p=0.95)

    @pytest.mark.parametrize("finish", ["length", "stop", "cancel"])
    @pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
    def test_the_tokens_are_the_recorded_ones(self, lm, lm_params, sampled, finish):
        from tpu_dist.observe import spans

        N = 14
        prompts = models.synthetic_tokens(5, 6, 64, seed=21)
        sampling = [serve.SamplingParams(seed=40 + i, **self.SAMPLED) if sampled else None
                    for i in range(len(prompts))]
        if sampled:
            want = [_alone(lm, lm_params, _cfg(max_batch=2), np.asarray(p), N, sp)
                    for p, sp in zip(prompts, sampling)]
        else:
            want = list(np.asarray(lm.generate(lm_params, prompts, N, cache_len=32)))
        # request 1 is the one that ends early; its stop token is one its
        # own stream holds past the first token and before the last
        cut = next(i for i in range(2, N - 2) if want[1][i] not in want[1][:i])
        stop = int(want[1][cut]) if finish == "stop" else None
        ttft0, tpot0 = _histograms()
        t0 = spans.time.perf_counter()
        eng = serve.ServeEngine(lm, lm_params, _cfg(max_batch=2))
        rids = [eng.submit(np.asarray(p), N, sampling=sp, stop_token=stop if i == 1 else None)
                for i, (p, sp) in enumerate(zip(prompts, sampling))]
        reqs = list(eng.queue)
        if finish == "cancel":
            while len(reqs[1].tokens) < cut + 1:
                eng.step()
            assert eng._unread is not None and any(r is reqs[1] for _, r in eng._unread[1])
            assert eng.cancel(rids[1])
        res = eng.run_until_drained()
        early = {"length": "length", "stop": "stop", "cancel": "cancelled"}[finish]
        for i, rid in enumerate(rids):
            r = res[rid]
            if i == 1 and finish != "length":
                assert r.finish_reason == early and r.emitted == cut + 1
            else:
                assert r.finish_reason == "length" and r.emitted == N
            np.testing.assert_array_equal(r.tokens, want[i][: r.emitted])
            # the overrun is in no token list and no time list
            assert reqs[i].tokens == r.tokens.tolist()
            assert len(r.token_times) == len(reqs[i].token_times) == r.emitted
        emitted = sum(res[rid].emitted for rid in rids)
        ttft1, tpot1 = _histograms()
        assert ttft1 - ttft0 == len(rids) and tpot1 - tpot0 == emitted - len(rids)
        # every token after a request's first came from a decode step; the
        # one step more is the overrun, and only a stop or a cancel has one
        fed = sum(s.attrs["fed"] for s in spans.recent(since=t0)
                  if s.name == "engine.decode_dispatch")
        assert fed == emitted - len(rids) + (finish != "length")
        assert eng.allocator.used == 0

    @pytest.mark.parametrize("kind", ["paged", "recurrent", "ring"])
    def test_a_freed_slot_serves_its_next_tenant_exactly(self, served_models, kind):
        """One slot and just the blocks one request needs: every request
        lives where the last one lived, admitted the step after it left,
        behind a stop's or a cancel's overrun step."""
        model, params, vocab = served_models(kind)
        cfg = serve.ServeConfig(max_batch=1, block_size=8, num_blocks=9, max_seq=72,
                                prefill_chunk=16)
        N = 30   # past the ring's 24 rows
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, vocab, (n,), dtype=np.int32) for n in (23, 40, 9, 33)]
        want = [_alone(model, params, cfg, p, N) for p in prompts]
        assert len({w.tolist()[-1] for w in want}) > 1, "the answers differ"
        cut = next(i for i in range(8, N - 2) if want[0][i] not in want[0][:i])
        eng = serve.ServeEngine(model, params, cfg)
        rids = [eng.submit(p, N, stop_token=int(want[0][cut]) if i == 0 else None)
                for i, p in enumerate(prompts)]
        reqs = list(eng.queue)
        while len(reqs[1].tokens) < 12:
            eng.step()
        assert eng.cancel(rids[1])
        res = eng.run_until_drained()
        np.testing.assert_array_equal(res[rids[0]].tokens, want[0][: cut + 1])
        np.testing.assert_array_equal(res[rids[1]].tokens, want[1][:12])
        np.testing.assert_array_equal(res[rids[2]].tokens, want[2])
        np.testing.assert_array_equal(res[rids[3]].tokens, want[3])
        assert [res[r].finish_reason for r in rids] == ["stop", "cancelled", "length", "length"]
        admits = {a[1]: a for a in eng.audit if a[0] == "admit"}
        finishes = {a[1]: a for a in eng.audit if a[0] == "finish"}
        for before, after in zip(rids, rids[1:]):
            # (admit, id, slot, blocks, step) / (finish, id, reason, emitted, step)
            assert admits[after][2] == admits[before][2] == 0
            assert set(admits[after][3]) & set(admits[before][3])
            # a cancel is applied where the next call begins, ahead of its
            # admission; any other finish where a call reads its step
            same_call = finishes[before][2] == "cancelled"
            assert admits[after][4] == finishes[before][4] + (not same_call)

    @staticmethod
    def _churn(lm, lm_params, after_each_step):
        """Answers of 2-9 tokens on three slots, a stop token and a cancel
        among them, one prefill row a round; the engine runs empty, then
        serves one request more.  -> (engine, request ids)"""
        eng = serve.ServeEngine(lm, lm_params, _cfg(max_batch=3, prefill_batch=1))

        def drain():
            while eng.pending:
                eng.step()
                after_each_step(eng)

        rng = np.random.default_rng(3)
        rids = []
        for i in range(16):
            prompt = models.synthetic_tokens(1, int(rng.integers(2, 12)), 64, seed=i)[0]
            rids.append(eng.submit(np.asarray(prompt), int(rng.integers(2, 10)),
                                   stop_token=7 if i % 5 == 0 else None))
        for _ in range(9):
            eng.step()
            after_each_step(eng)
        running = next(r for r in eng.slots if r is not None and r.state == "decode")
        assert eng.cancel(running.request_id)
        drain()
        rids.append(eng.submit(np.asarray(models.synthetic_tokens(1, 5, 64, seed=99)[0]), 6))
        drain()
        return eng, rids

    def test_the_devices_rows_are_the_hosts_but_one_step_on(self, lm, lm_params):
        """After every call: a row the host has not marked stale reads on
        the device what the host's mirrors say (block table, sampling
        columns, fed or not), with position and counter one on where its
        slot is in the step still unread; a stale row is the host's to
        write before the next launch, whatever the device holds."""
        checked = [0, 0]

        def check(eng):
            MB = eng.blocks_per_seq
            ints = np.asarray(eng._dint)
            fresh = ~eng._stale
            fed = eng.active & (eng._unfed > 0)
            ahead = np.zeros_like(eng.index)
            if eng._unread is not None:
                for slot, req in eng._unread[1]:
                    ahead[slot] += req.state != "finished"
            np.testing.assert_array_equal(ints[fresh, :MB], eng.block_tables[fresh])
            np.testing.assert_array_equal(ints[fresh, MB + eng._ACTIVE], fed[fresh])
            live = fresh & eng.active
            for col, mirror in ((eng._INDEX, eng.index), (eng._COUNTER, eng.counters)):
                np.testing.assert_array_equal(ints[live, MB + col], (mirror + ahead)[live])
            np.testing.assert_array_equal(ints[live, MB + eng._SEED], eng.seeds[live])
            checked[0] += int(live.sum())
            checked[1] += int(eng._stale.sum())

        eng, rids = self._churn(lm, lm_params, check)
        assert checked[0] > 40 and checked[1] > 15, checked
        assert len(eng.results) == len(rids) and eng.allocator.used == 0

    def test_ahead_on_every_dispatch_the_causes_do_not_name(self, lm, lm_params):
        """Slots join and leave every few steps (answers of 2-9 tokens on
        three slots, a stop and a cancel among them): a stale row does not
        drain the look-ahead.  The dispatches that were not ahead are the
        first after the engine ran empty and those behind a step skipped
        for prefill; the counters say what the spans say."""
        from tpu_dist.observe import spans
        from tpu_dist.observe.registry import REGISTRY

        counter = lambda n: REGISTRY.counter(f"tpu_dist_serve_{n}_total")  # noqa: E731
        causes = ("drained", "prefill_priority", "prefill_join")
        before = {n: counter(n).value() for n in ("decode_steps", "decode_ahead", "state_repacks")}
        gaps0 = {c: counter("decode_gaps").value(cause=c) for c in causes}
        t0 = spans.time.perf_counter()
        eng, rids = self._churn(lm, lm_params, lambda eng: None)
        got = spans.recent(since=t0)
        steps = [s for s in got if s.name == "engine.step"]
        kids = {s.id: [c for c in got if c.parent == s.id] for s in steps}
        launched = [(n, d) for n, s in enumerate(steps) for d in kids[s.id]
                    if d.name == "engine.decode_dispatch"]
        assert len(launched) > 30
        behind = [(n, d) for n, d in launched if not d.attrs["ahead"]]
        for n, d in launched:
            names = [c.name for c in kids[steps[n - 1].id]] if n else []
            gap = d.attrs.get("gap")
            # ahead: the call before launched a step that this call reads
            assert d.attrs["ahead"] == ("engine.decode_dispatch" in names)
            assert d.attrs["ahead"] == ("engine.decode_wait" in [c.name for c in kids[steps[n].id]])
            if not d.attrs["ahead"]:
                assert gap in ("drained", "prefill_priority")
            elif gap is not None:
                # the call before waited for a round's first tokens, and
                # with them for the step it had launched ahead of the round
                assert gap == "prefill_join" and "engine.prefill_wait" in names
            else:
                assert "engine.prefill_wait" not in names
        # slots joined and left under steps that stayed ahead
        assert sum(d.attrs["repacked"] and d.attrs["ahead"] for _, d in launched) >= 10
        assert any(d.attrs.get("gap") == "prefill_join" for _, d in launched)
        assert behind[0] == launched[0]
        assert behind[-1][1].attrs["gap"] == "drained"   # it ran empty, then served again
        assert len(behind) < len(launched) // 4
        delta = {n: counter(n).value() - v for n, v in before.items()}
        assert delta["decode_steps"] == len(launched) == eng.steps_with_decode
        assert delta["decode_ahead"] == len(launched) - len(behind)
        assert delta["state_repacks"] == sum(d.attrs["repacked"] for _, d in launched)
        for c in causes:
            assert (counter("decode_gaps").value(cause=c) - gaps0[c]
                    == sum(d.attrs.get("gap") == c for _, d in launched))
        assert all(eng.results[r].finish_reason in ("length", "stop", "cancelled") for r in rids)

    @pytest.mark.parametrize("finish", ["length", "stop", "cancel"])
    def test_drained_leaves_no_step_unread(self, lm, lm_params, finish):
        """`pending` holds until the last token of the last request is
        applied AND nothing is in flight: a stop's or a cancel's overrun
        step is read (and dropped) before the engine calls itself idle."""
        prompt = np.asarray(models.synthetic_tokens(1, 5, 64, seed=3)[0])
        free = np.asarray(lm.generate(lm_params, prompt[None], 12, cache_len=32))[0]
        cut = next(i for i in range(2, 10) if free[i] not in free[:i])
        eng = serve.ServeEngine(lm, lm_params, _cfg())
        rid = eng.submit(prompt, 12, stop_token=int(free[cut]) if finish == "stop" else None)
        req = eng.queue[0]
        reads = 0
        while eng.pending:
            had = eng._unread is not None
            if finish == "cancel" and len(req.tokens) == cut + 1 and req.state == "decode":
                eng.cancel(rid)
            eng.step()
            reads += had
            if req.state == "finished" and finish != "length" and eng._unread is not None:
                # the overrun is still to be read: not idle yet
                assert eng.pending and not any(r is not None for r in eng.slots)
        assert eng._unread is None and not eng.pending
        assert reads == eng.steps_with_decode
        want = 12 if finish == "length" else cut + 1
        assert eng.results[rid].emitted == want
        np.testing.assert_array_equal(eng.results[rid].tokens, free[:want])
        # emitted - 1 decode steps gave tokens; a stop or a cancel ran one more
        assert eng.steps_with_decode == want - 1 + (finish != "length")
        calls = eng.step_count
        eng.run_until_drained()   # idle: nothing left to call for
        assert eng.step_count == calls
