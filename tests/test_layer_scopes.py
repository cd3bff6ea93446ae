"""The program's layer scopes (``jax.named_scope("malstone.<layer>")``) in
the compiled streaming job: each names the instructions of its layer, and
they change nothing but names.

The job is lowered at test size on one device for a seed and a log source
under the streams and MapReduce middlewares. The benchmark's trace
reduction reads these names from each instruction's ``op_name`` metadata.
"""

import contextlib
import re

import jax
import pytest
from jax._src.lib import xla_client

from repro.common import env
from repro.common.types import ExchangePlan
from repro.core import run
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, generate_chunked_log, make_seed_streaming

CFG = MalGenConfig(num_sites=301, num_entities=1000,
                   marked_site_fraction=0.2, marked_event_fraction=0.3)
NUM_CHUNKS, CHUNK = 4, 512
CASES = [(source, backend) for source in ("seed", "log")
         for backend in ("streams", "mapreduce")]
# the layers each source runs; every middleware runs the exchange scope
# (the streams psum, the MapReduce rounds) even on one device
SCOPES = {"seed": {"malstone.generate", "malstone.combine",
                   "malstone.exchange", "malstone.finalize"},
          "log": {"malstone.read", "malstone.combine", "malstone.exchange",
                  "malstone.finalize"}}


@pytest.fixture(scope="module")
def inputs():
    seed = make_seed_streaming(jax.random.key(3), CFG, NUM_CHUNKS, CHUNK)
    return {"seed": seed,
            "log": generate_chunked_log(seed, CFG, NUM_CHUNKS, CHUNK)}


def compiled_job(program_input, source, backend):
    """The benchmark's job: ``repro.core.run``, streaming engine,
    MalStone B, compiled for this process's first device."""
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    kwargs = {"num_chunks": NUM_CHUNKS, "cfg": CFG} if source == "seed" \
        else {}

    def malstone_job(x):
        return run(x, CFG.num_sites, mesh=mesh, engine="streaming",
                   backend=backend, statistic="B", chunk_records=CHUNK,
                   plan=ExchangePlan(), return_shuffle_stats=True, **kwargs)

    return jax.jit(malstone_job).lower(program_input).compile()


def without_metadata(compiled) -> str:
    """The compiled HLO without metadata, every numbered name renumbered in
    order of first appearance: XLA's passes number the instructions they
    make from counters that an instruction's metadata can advance."""
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_metadata = False
    text = "\n".join(m.to_string(opts)
                     for m in compiled.runtime_executable().hlo_modules())
    names = {}
    return re.sub(r"\b[A-Za-z_][\w\-]*\.\d+(?:\.[\w\-]+)*\b",
                  lambda m: names.setdefault(m.group(0), f"n{len(names)}"),
                  text)


def scopes_in(compiled) -> set:
    """The ``malstone.*`` scopes in the instructions' op paths."""
    return {scope for path in re.findall(r'op_name="([^"]*)"',
                                         compiled.as_text())
            for scope in re.findall(r"(?:^|/)(malstone\.\w+)", path)}


@pytest.mark.parametrize("source,backend", CASES)
def test_each_layer_is_named_where_it_runs(inputs, source, backend):
    assert scopes_in(compiled_job(inputs[source], source, backend)) == \
        SCOPES[source]


@pytest.mark.parametrize("source,backend", CASES)
def test_scopes_change_nothing_but_names(inputs, source, backend,
                                         monkeypatch):
    scoped = without_metadata(compiled_job(inputs[source], source, backend))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled_job(inputs[source], source, backend)
    assert not scopes_in(plain)
    assert without_metadata(plain) == scoped


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    """The program's persistent compile cache in an empty directory, keeping
    every program however quick its compile; JAX's settings restored after."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_hlo_source_file_canonicalization_regex",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv(env.COMPILE_CACHE_ENV, str(tmp_path))
    compilation_cache.reset_cache()
    env.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    yield tmp_path
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_cached_job_carries_its_own_scopes(inputs, fresh_cache):
    """A job found in the compile cache shows the op paths of the program
    that asked for it, not those of a program that differs from it only in
    names and was compiled first."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        assert not scopes_in(compiled_job(inputs["log"], "log", "streams"))
    assert any(fresh_cache.iterdir())
    assert scopes_in(compiled_job(inputs["log"], "log", "streams")) == \
        SCOPES["log"]
