"""Bootstrap config: the MASTER_ADDR/PORT/WORLD_SIZE/RANK env contract
(tuto.md:421-428 analog) and 2-D-mesh collective coverage."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from tpu_dist import comm
from tpu_dist.comm.init import InitConfig


class TestInitConfig:
    def test_from_env_full(self, monkeypatch):
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        monkeypatch.setenv("MASTER_PORT", "29500")
        monkeypatch.setenv("WORLD_SIZE", "4")
        monkeypatch.setenv("RANK", "2")
        cfg = InitConfig.from_env()
        assert cfg.coordinator_address == "10.0.0.1:29500"
        assert cfg.num_processes == 4
        assert cfg.process_id == 2

    def test_from_env_empty(self, monkeypatch):
        for var in ("MASTER_ADDR", "MASTER_PORT", "WORLD_SIZE", "RANK"):
            monkeypatch.delenv(var, raising=False)
        cfg = InitConfig.from_env()
        assert cfg.coordinator_address is None
        assert cfg.num_processes is None
        assert cfg.process_id is None

    def test_compile_cache_env_set_leaves_config_untouched(
        self, monkeypatch, tmp_path
    ):
        """JAX_COMPILATION_CACHE_DIR set: the operator owns the location.
        The helper names it and sets NOTHING in code — no directory, no
        thresholds."""
        from tpu_dist.utils import platform as platform_mod

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        keys = (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs",
        )
        before = {k: getattr(jax.config, k) for k in keys}
        assert platform_mod.setup_compile_cache() == str(tmp_path / "c")
        assert {k: getattr(jax.config, k) for k in keys} == before

    def test_compile_cache_default_is_fixed_path_under_checkout(
        self, monkeypatch, tmp_path
    ):
        """Env unset: the cache is at <checkout>/.jax_cache on every
        call (the directory is part of the cache key — a path built from
        a temp name, pid or time never hits), thresholds at JAX's
        defaults; a second compile of the same program is a cache HIT
        surfaced as a compile_cache event and a registry counter."""
        import os
        from pathlib import Path

        from jax._src import compilation_cache as _cc

        from tpu_dist.observe import events, registry
        from tpu_dist.utils import platform as platform_mod

        repo = Path(__file__).resolve().parent.parent
        assert platform_mod.DEFAULT_COMPILE_CACHE == repo / ".jax_cache"
        # the test must not fill the checkout's real cache (the chip tool
        # copies the tree as it stands): same code path, scratch target
        cache_dir = tmp_path / ".jax_cache"
        monkeypatch.setattr(platform_mod, "DEFAULT_COMPILE_CACHE", cache_dir)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        tdir = tmp_path / "telemetry"
        monkeypatch.setenv(events.ENV_DIR, str(tdir))
        monkeypatch.delenv(events.ENV_RUN_ID, raising=False)
        prev_secs = jax.config.jax_persistent_cache_min_compile_time_secs
        hits = registry.REGISTRY.counter("tpu_dist_compile_cache_hits_total")
        hits_before = hits.value()
        try:
            assert platform_mod.setup_compile_cache() == str(cache_dir)
            assert platform_mod.setup_compile_cache() == str(cache_dir)
            assert jax.config.jax_compilation_cache_dir == str(cache_dir)
            assert (
                jax.config.jax_persistent_cache_min_compile_time_secs
                == prev_secs
            ), "thresholds stay at JAX's defaults"
            # the default threshold declines sub-second programs; lower
            # it HERE (test only) so a tiny program exercises the wiring
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0
            )
            # two distinct jit objects over the same program: the second
            # compile must be served from the persistent cache
            jax.jit(lambda x: x * 3 + 1)(jnp.ones(8)).block_until_ready()
            assert os.listdir(cache_dir), "no compiled program persisted"
            jax.jit(lambda x: x * 3 + 1)(jnp.ones(8)).block_until_ready()
            assert hits.value() > hits_before
            recs = events.read_events(str(tdir))
            outcomes = {
                r["outcome"] for r in recs if r["event"] == "compile_cache"
            }
            assert {"hit", "miss"} <= outcomes
            n, errors = events.validate_dir(str(tdir))
            assert errors == []
        finally:
            # Full de-pollution: cache off, threshold restored, the
            # memoized cache dropped, and the hit/miss listener
            # unregistered so later tests' event files stay clean.
            jax.config.update("jax_compilation_cache_dir", None)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", prev_secs
            )
            _cc.reset_cache()
            jax.monitoring.clear_event_listeners()

    def test_file_init_rejects_multihost_master_addr(self, monkeypatch, tmp_path):
        # file:// rendezvous publishes a loopback coordinator, so an
        # off-host MASTER_ADDR signals a job it cannot serve: fail at
        # bootstrap, not as a later jax.distributed hang.
        import pytest

        # TEST-NET-3 address: guaranteed to resolve off-host everywhere
        monkeypatch.setenv("MASTER_ADDR", "203.0.113.7")
        monkeypatch.delenv("MASTER_PORT", raising=False)
        monkeypatch.setenv("TPU_DIST_INIT_METHOD", f"file://{tmp_path}/rdzv")
        import importlib

        init_mod = importlib.import_module("tpu_dist.comm.init")
        monkeypatch.setattr(init_mod, "_initialized", False)
        with pytest.raises(ValueError, match="single-host only"):
            comm.init(num_processes=2, process_id=0)

    def test_launchers_refuse_multiprocess_off_cpu(self, monkeypatch, capsys):
        """`comm.launch` and `python -m tpu_dist.run` put every child on
        THIS host — the CPU loopback harness.  With world > 1 and any
        other platform each child would claim every chip and the gang
        would hang, so both refuse up front and say what to do."""
        import pytest

        from tpu_dist import run as run_mod

        for platform in ("tpu", None):
            with pytest.raises(ValueError, match="ONE process per host"):
                comm.launch(print, 2, platform=platform)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("TPU_DIST_PLATFORM", raising=False)
        with pytest.raises(SystemExit) as exc:
            run_mod.main(["--nproc", "2", "nonexistent.py"])
        assert exc.value.code == 2
        assert "CPU loopback harness" in capsys.readouterr().err
        with pytest.raises(SystemExit):
            run_mod.main(["--nproc", "2", "--platform", "tpu", "x.py"])

    def test_addr_without_port_ignored(self, monkeypatch):
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        monkeypatch.delenv("MASTER_PORT", raising=False)
        cfg = InitConfig.from_env()
        assert cfg.coordinator_address is None


class Test2DMeshCollectives:
    """Collectives over ONE axis of a 2-D mesh: partial reductions —
    the sub-communicator pattern (row/column groups)."""

    def _run(self, fn, in_specs, out_specs):
        mesh = comm.make_mesh((2, 4), ("row", "col"), platform="cpu")
        mapped = jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )
        )
        return mesh, mapped

    def test_partial_all_reduce_over_col(self):
        def fn():
            val = (
                lax.axis_index("row") * 10 + lax.axis_index("col")
            ).astype(jnp.float32)
            return comm.all_reduce(val, axis_name="col").reshape(1, 1)

        mesh, mapped = self._run(fn, (), P("row", "col"))
        out = np.asarray(mapped())
        # row r: sum over col of (10r + c) = 40r + 6
        for r in range(2):
            np.testing.assert_allclose(out[r], np.full(4, 40 * r + 6))

    def test_ring_over_row_axis(self):
        from tpu_dist import parallel

        def fn():
            val = (lax.axis_index("row") + 1).astype(jnp.float32).reshape(1)
            return parallel.ring_all_reduce(val, "row").reshape(1, 1)

        mesh, mapped = self._run(fn, (), P("row", "col"))
        np.testing.assert_allclose(np.asarray(mapped()), 3.0)

    def test_shift_over_col_axis(self):
        def fn():
            val = lax.axis_index("col").astype(jnp.float32).reshape(1)
            return comm.shift(val, 1, axis_name="col").reshape(1, 1)

        mesh, mapped = self._run(fn, (), P("row", "col"))
        out = np.asarray(mapped())  # (2, 4): rows x shifted col indices
        for r in range(2):
            np.testing.assert_allclose(out[r], (np.arange(4) - 1) % 4)
