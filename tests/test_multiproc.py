"""Multi-process launch stack tests.

- ``repro.common.env``: preparse semantics and the XLA flag helpers. The
  pytest process's backend is already up, so the live force/add paths are
  exercised through their refusal guards; the string-surgery paths run
  with ``xla_backend_initialized`` monkeypatched off.
- ``repro.launch.coordinator``: the three launch modes of ``DistConfig``,
  preparse, and ``spawn_local``'s rank wiring / status propagation
  (cheap ``-c`` children — no jax in the workers).
- ``repro.launch.mesh``: the ``make_host_mesh`` multi-axis regression and
  ``replicate_to_mesh`` on a single-device mesh.
- ``repro.core.overlap``: ``OverlapStreamingRunner`` bit-identity against
  the single-jit scan engine — histograms AND every ``ShuffleStats``
  field, with ``overlap`` on and off (single device; the real 2-process
  gang is the slow subprocess test below).
- the gang itself: ``tests/md_scripts/multiproc_check.py`` forks two
  one-device workers joined through ``jax.distributed`` and compares every
  result bitwise against the forced-2-device single-process oracle.
"""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.common import env
from repro.common.types import ExchangePlan
from repro.core import OverlapStreamingRunner, malstone_run_streaming, run
from repro.launch import coordinator
from repro.launch.mesh import (
    make_global_mesh,
    make_host_mesh,
    replicate_to_mesh,
)
from repro.malgen import MalGenConfig, make_seed_streaming

HERE = pathlib.Path(__file__).parent
SRC = str(HERE.parent / "src")

STATS_FIELDS = ("rounds", "capacity", "sent", "residual", "overflow",
                "bytes_exchanged")


# ------------------------------------------------------------ repro.common.env
class TestPreparse:
    def test_last_occurrence_wins(self):
        argv = ["prog", "--nodes", "2", "--nodes=5"]
        assert env.preparse_flag("--nodes", None, argv) == "5"
        assert env.preparse_int_flag("--nodes", None, argv) == 5

    def test_space_and_eq_forms(self):
        assert env.preparse_flag("--x", None, ["p", "--x", "a"]) == "a"
        assert env.preparse_flag("--x", None, ["p", "--x=b"]) == "b"

    def test_default_when_absent(self):
        assert env.preparse_flag("--x", "d", ["p"]) == "d"
        assert env.preparse_int_flag("--x", 7, ["p"]) == 7
        assert env.preparse_nodes(argv=["p"]) == 2

    def test_trailing_flag_without_value_ignored(self):
        assert env.preparse_flag("--x", "d", ["p", "--x"]) == "d"


class TestXlaFlags:
    def test_backend_up_refuses_everything(self, monkeypatch):
        jax.device_count()  # make sure the backend really is initialized
        assert env.xla_backend_initialized()
        monkeypatch.setenv("XLA_FLAGS", "--untouched=1")
        assert env.force_host_devices(8) is False
        assert env.add_xla_flags(("--some_flag=1",)) is False
        assert os.environ["XLA_FLAGS"] == "--untouched=1"

    def test_force_string_surgery(self, monkeypatch):
        monkeypatch.setattr(env, "xla_backend_initialized", lambda: False)
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        assert env.force_host_devices(1) is False       # n<=1 is a no-op
        assert env.force_host_devices(4, extra="--e=1") is True
        assert os.environ["XLA_FLAGS"] == (
            f"{env.FORCE_DEVICES_FLAG}=4 --e=1")
        # already forced -> the explicit earlier setting wins
        assert env.force_host_devices(8) is False
        assert f"{env.FORCE_DEVICES_FLAG}=4" in os.environ["XLA_FLAGS"]

    def test_force_respect_existing(self, monkeypatch):
        monkeypatch.setattr(env, "xla_backend_initialized", lambda: False)
        monkeypatch.setenv("XLA_FLAGS", "--caller_set=1")
        assert env.force_host_devices(4, respect_existing=True) is False
        assert os.environ["XLA_FLAGS"] == "--caller_set=1"
        monkeypatch.delenv("XLA_FLAGS")
        assert env.force_host_devices(4, respect_existing=True) is True
        assert os.environ["XLA_FLAGS"] == f"{env.FORCE_DEVICES_FLAG}=4"

    def test_clear_forced_devices(self, monkeypatch):
        monkeypatch.setenv(
            "XLA_FLAGS", f"{env.FORCE_DEVICES_FLAG}=8 --keep=1")
        assert env.clear_forced_devices() is True
        assert os.environ["XLA_FLAGS"] == "--keep=1"
        assert env.clear_forced_devices() is False

    def test_latency_hiding_flags_known_platforms_only(self):
        assert env.latency_hiding_flags("gpu")
        assert all(f.startswith("--xla_gpu_")
                   for f in env.latency_hiding_flags("gpu"))
        assert env.latency_hiding_flags("cpu") == ()
        assert env.latency_hiding_flags("tpu") == ()
        assert env.latency_hiding_flags("no_such_platform") == ()


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_cache_dir(self):
        keys = ("jax_compilation_cache_dir",
                "jax_compilation_cache_include_metadata_in_key",
                "jax_hlo_source_file_canonicalization_regex")
        was = {k: getattr(jax.config, k) for k in keys}
        yield
        for k, v in was.items():
            jax.config.update(k, v)

    def test_env_var_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv(env.COMPILE_CACHE_ENV, str(tmp_path))
        assert env.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    def test_fixed_repo_path_otherwise(self, monkeypatch):
        monkeypatch.delenv(env.COMPILE_CACHE_ENV, raising=False)
        path = env.enable_compile_cache()
        assert path == str(pathlib.Path(SRC).parent / ".jax_cache")
        assert env.enable_compile_cache() == path  # no pid, tmp or time


class TestOneProcessPerChip:
    @pytest.mark.parametrize("platforms", [None, "", "tpu", "cpu,tpu"])
    def test_gang_refused_unless_forced_to_cpu(self, monkeypatch, platforms):
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(RuntimeError, match="one mesh"):
            coordinator.spawn_local(coordinator.DistConfig(num_processes=2),
                                    ["-c", "pass"], timeout=60)

    def test_gang_allowed_on_cpu(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", " CPU ")
        env.refuse_gang_off_cpu("test")  # does not raise


# ----------------------------------------------------- repro.launch.coordinator
class TestDistConfig:
    def test_single_process_default(self):
        cfg = coordinator.DistConfig()
        assert not cfg.is_distributed
        assert not cfg.is_spawn_parent
        assert not cfg.is_worker

    def test_spawn_parent_vs_worker(self):
        parent = coordinator.DistConfig(num_processes=2)
        assert parent.is_distributed and parent.is_spawn_parent
        assert not parent.is_worker
        worker = coordinator.DistConfig(num_processes=2, process_id=0)
        assert worker.is_distributed and worker.is_worker
        assert not worker.is_spawn_parent

    def test_preparse(self):
        cfg = coordinator.preparse(
            ["p", "--num-processes", "2", "--process-id", "1",
             "--coordinator", "127.0.0.1:1234"])
        assert cfg == coordinator.DistConfig(
            num_processes=2, process_id=1, coordinator="127.0.0.1:1234")

    def test_preparse_defaults(self):
        assert coordinator.preparse(["p"]) == coordinator.DistConfig()

    def test_banner(self):
        assert coordinator.process_banner(
            coordinator.DistConfig()) == "single-process"
        assert "1/2" in coordinator.process_banner(
            coordinator.DistConfig(2, 1, "h:1"))


class TestCoordinator:
    def test_pick_port_is_bindable(self):
        import socket
        port = coordinator.pick_port()
        assert 0 < port < 65536
        with socket.socket() as s:
            s.bind(("127.0.0.1", port))

    def test_initialize_rejects_spawn_parent(self):
        with pytest.raises(ValueError, match="spawn parent"):
            coordinator.initialize(coordinator.DistConfig(num_processes=2))

    def test_initialize_requires_coordinator_for_worker(self):
        with pytest.raises(ValueError, match="--coordinator"):
            coordinator.initialize(
                coordinator.DistConfig(num_processes=2, process_id=0))

    def test_initialize_validates_rank_range(self):
        with pytest.raises(ValueError, match="out of range"):
            coordinator.initialize(coordinator.DistConfig(
                num_processes=2, process_id=5, coordinator="h:1"))

    def test_bootstrap_rejects_uneven_node_split(self):
        with pytest.raises(SystemExit, match="divide evenly"):
            coordinator.bootstrap(
                ["p", "--num-processes", "2", "--process-id", "0",
                 "--coordinator", "h:1"], local_devices_for=3)

    def test_spawn_local_appends_ranks_and_propagates_status(self):
        # each cheap worker exits with its own appended rank: rank 1's
        # nonzero status must surface as the gang status
        script = ("import sys;"
                  "sys.exit(int(sys.argv[sys.argv.index('--process-id')+1]))")
        cfg = coordinator.DistConfig(num_processes=2)
        assert coordinator.spawn_local(cfg, ["-c", script], timeout=60) == 1

    def test_spawn_local_all_ok(self):
        cfg = coordinator.DistConfig(num_processes=2)
        assert coordinator.spawn_local(cfg, ["-c", "pass"], timeout=60) == 0


# ----------------------------------------------------------- repro.launch.mesh
class TestHostMesh:
    def test_multi_axis_without_shape_raises(self):
        # regression: the old implementation built an EMPTY mesh shape for
        # len(axes) > 1, which can never match the axis names
        with pytest.raises(ValueError, match="explicit shape"):
            make_host_mesh(8, axes=("data", "model"))

    def test_shape_product_must_match(self):
        with pytest.raises(ValueError, match="places"):
            make_host_mesh(8, axes=("data", "model"), shape=(2, 2))

    def test_single_device_meshes(self):
        assert make_host_mesh(1).shape == {"data": 1}
        m = make_host_mesh(1, axes=("a", "b"), shape=(1, 1))
        assert m.shape == {"a": 1, "b": 1}

    def test_global_mesh_single_process(self):
        m = make_global_mesh()
        assert m.axis_names == ("data",)
        assert m.devices.size == jax.device_count()
        with pytest.raises(ValueError, match="1-D"):
            make_global_mesh(axes=("a", "b"))

    def test_replicate_to_mesh(self):
        mesh = make_global_mesh()
        tree = {"a": np.arange(6, dtype=np.int32).reshape(2, 3),
                "key": jax.random.key(3), "static": 17}
        out = replicate_to_mesh(tree, mesh)
        assert out["static"] == 17
        np.testing.assert_array_equal(np.asarray(out["a"]), tree["a"])
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(out["key"])),
            np.asarray(jax.random.key_data(tree["key"])))


# ---------------------------------------------------------- repro.core.overlap
@pytest.fixture(scope="module")
def overlap_setup():
    cfg = MalGenConfig(num_sites=64, num_entities=256,
                       marked_site_fraction=0.2, marked_event_fraction=0.3)
    num_chunks, chunk_records = 2, 256
    seed = make_seed_streaming(jax.random.key(11), cfg, num_chunks,
                               chunk_records)
    mesh = make_global_mesh()
    return cfg, seed, mesh, num_chunks, chunk_records


class TestOverlapRunner:
    def _scan_oracle(self, setup, backend, want_stats):
        cfg, seed, mesh, num_chunks, chunk_records = setup
        out = jax.jit(lambda s: run(
            s, cfg.num_sites, mesh=mesh, engine="streaming",
            chunk_records=chunk_records, cfg=cfg, num_chunks=num_chunks,
            backend=backend, statistic="B",
            return_shuffle_stats=want_stats))(seed)
        return out if want_stats else (out, None)

    @pytest.mark.parametrize("backend", ["streams", "mapreduce"])
    @pytest.mark.parametrize("overlap", [True, False])
    def test_bit_identical_to_scan(self, overlap_setup, backend, overlap):
        cfg, seed, mesh, num_chunks, chunk_records = overlap_setup
        want_stats = backend == "mapreduce"
        ref, ref_stats = self._scan_oracle(overlap_setup, backend,
                                           want_stats)
        runner = OverlapStreamingRunner(
            seed, cfg, mesh=mesh, num_chunks=num_chunks,
            chunk_records=chunk_records, backend=backend)
        res, stats = runner.run_result("B", overlap=overlap)
        np.testing.assert_array_equal(np.asarray(res.rho),
                                      np.asarray(ref.rho))
        if want_stats:
            for f in STATS_FIELDS:
                assert int(getattr(stats, f)) == int(getattr(ref_stats, f)), f
        else:
            assert stats is None

    @pytest.mark.parametrize("impl", ["sort", "counting"])
    def test_exchange_impls_match_scan(self, overlap_setup, impl):
        cfg, seed, mesh, num_chunks, chunk_records = overlap_setup
        plan = ExchangePlan(impl=impl)
        ref, ref_stats = jax.jit(lambda s: run(
            s, cfg.num_sites, mesh=mesh, engine="streaming",
            chunk_records=chunk_records, cfg=cfg, num_chunks=num_chunks,
            backend="mapreduce", statistic="B", plan=plan,
            return_shuffle_stats=True))(seed)
        runner = OverlapStreamingRunner(
            seed, cfg, mesh=mesh, num_chunks=num_chunks,
            chunk_records=chunk_records, backend="mapreduce", plan=plan)
        res, stats = runner.run_result("B", overlap=True)
        np.testing.assert_array_equal(np.asarray(res.rho),
                                      np.asarray(ref.rho))
        for f in STATS_FIELDS:
            assert int(getattr(stats, f)) == int(getattr(ref_stats, f)), f

    def test_convenience_path_matches(self, overlap_setup):
        cfg, seed, mesh, num_chunks, chunk_records = overlap_setup
        ref, _ = self._scan_oracle(overlap_setup, "streams", False)
        res = malstone_run_streaming(
            seed, cfg.num_sites, mesh=mesh, backend="streams",
            chunk_records=chunk_records, cfg=cfg, num_chunks=num_chunks,
            overlap=True)
        np.testing.assert_array_equal(np.asarray(res.rho),
                                      np.asarray(ref.rho))

    def test_rejects_bad_backend(self, overlap_setup):
        cfg, seed, mesh, num_chunks, chunk_records = overlap_setup
        with pytest.raises(ValueError, match="backend"):
            OverlapStreamingRunner(
                seed, cfg, mesh=mesh, num_chunks=num_chunks,
                chunk_records=chunk_records, backend="no_such")

    def test_overlap_requires_seed_mode(self, overlap_setup):
        cfg, _, mesh, num_chunks, chunk_records = overlap_setup
        from repro.malgen import generate_chunked_log
        seed = make_seed_streaming(jax.random.key(11), cfg, num_chunks,
                                   chunk_records)
        log = generate_chunked_log(seed, cfg, num_chunks, chunk_records)
        with pytest.raises(ValueError, match="seed"):
            malstone_run_streaming(
                log, cfg.num_sites, mesh=mesh, chunk_records=chunk_records,
                num_chunks=num_chunks, overlap=True)


class TestSiteSamplingFusionDeterminism:
    """Regression: ``sample_sites_masked`` used to recompute the normalized
    float32 CDF inside every generation program, and XLA CPU compiled that
    reduction to slightly different values in different fusion contexts
    (eager vs outer-jit vs the overlap runner's standalone programs) —
    flipping boundary-adjacent uniform draws to the neighboring site (3 of
    131072 records at this exact shape, caught by the launcher's 2-process
    ``--check``). The CDFs now live in ``SeedInfo`` as values; this pins
    eager == jit == overlap-runner bit-identity at the shape that flipped.
    """

    def test_streaming_run_bit_identical_across_compile_contexts(self):
        cfg = MalGenConfig(num_sites=2048, num_entities=16384)
        num_chunks, chunk_records = 8, 16384
        seed = make_seed_streaming(jax.random.key(0), cfg, num_chunks,
                                   chunk_records)
        mesh = make_global_mesh()
        kw = dict(mesh=mesh, chunk_records=chunk_records, cfg=cfg,
                  num_chunks=num_chunks, backend="streams")
        eager = malstone_run_streaming(seed, cfg.num_sites, **kw)
        jitted = jax.jit(lambda s: malstone_run_streaming(
            s, cfg.num_sites, **kw))(seed)
        runner = OverlapStreamingRunner(
            seed, cfg, mesh=mesh, num_chunks=num_chunks,
            chunk_records=chunk_records, backend="streams")
        over, _ = runner.run_result("B", overlap=True)
        np.testing.assert_array_equal(np.asarray(eager.rho),
                                      np.asarray(jitted.rho))
        np.testing.assert_array_equal(np.asarray(eager.rho),
                                      np.asarray(over.rho))


# ----------------------------------------------------------- the 2-process gang
@pytest.mark.slow
def test_two_process_gang_bit_identical():
    ml_env = dict(os.environ)
    ml_env["PYTHONPATH"] = SRC + os.pathsep + ml_env.get("PYTHONPATH", "")
    ml_env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "md_scripts" / "multiproc_check.py")],
        capture_output=True, text=True, timeout=900, env=ml_env)
    assert proc.returncode == 0, (
        f"multiproc_check failed\nSTDOUT:\n{proc.stdout}\n"
        f"STDERR:\n{proc.stderr[-4000:]}")
    assert "MULTIPROC OK" in proc.stdout
