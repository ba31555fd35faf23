"""Run-mode rules: where Pallas kernels run, where compiles are cached, and
how strictly the serving engine is held to its oracle."""
import json

import jax
import numpy as np
import pytest

from repro.launch import serve
from repro.runtime import platform as PF
from repro.serving import engine as E


@pytest.mark.parametrize("backend,expected", [("cpu", True), ("tpu", False)])
def test_interpret_follows_backend(monkeypatch, backend, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert PF.resolve_interpret() is expected
    assert PF.resolve_interpret(None) is expected
    # an explicit choice is never overridden
    assert PF.resolve_interpret(not expected) is (not expected)


@pytest.mark.parametrize("backend", ["gpu", "METAL"])
def test_interpret_refuses_other_backends(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match=backend):
        PF.resolve_interpret()


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory setting after a test changes it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert PF.configure_compile_cache() == str(tmp_path)
    # the program sets no directory of its own: JAX reads the variable
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_inside_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = PF.configure_compile_cache()
    repo = PF.DEFAULT_CACHE_DIR.parent
    assert path == str(repo / ".jax_cache")
    assert (repo / "src" / "repro" / "runtime" / "platform.py").is_file()
    assert jax.config.jax_compilation_cache_dir == path
    assert PF.configure_compile_cache() == path          # fixed, not fresh


# serve --engine's parity gate follows the platform too: exact tokens on the
# CPU, a teacher-forced logit margin elsewhere (see serve.ENGINE_MARGIN_TOL)
SERVE_ARGV = ["--arch", "mla-7b", "--smoke", "--engine", "--backend", "ref",
              "--paged", "--batch", "4", "--prompt-lens", "32,64",
              "--prefill-chunk", "16", "--gen", "8", "--log-json"]


@pytest.fixture
def off_cpu(monkeypatch, tmp_path):
    """serve as it runs on a TPU (its margin gate), on the CPU's numerics;
    the variable keeps serve from setting a compile cache in this process."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_engine_parity_off_cpu_is_teacher_forced_margin(off_cpu, capsys):
    engine, results = serve.main(SERVE_ARGV)
    assert [r.status for r in results] == ["done"] * 4
    events = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if line.startswith("{")]
    (parity,) = [e for e in events if e["event"] == "engine_parity"]
    assert parity["parity"] == "margin" and parity["tokens"] == 4 * 8
    assert 0.0 <= parity["margin"] <= serve.ENGINE_MARGIN_TOL


def _rope_off_by_one(monkeypatch):
    dispatch = E.ServingEngine._dispatch_decode
    monkeypatch.setattr(E.ServingEngine, "_dispatch_decode",
                        lambda self, state, lens: dispatch(
                            self, state, np.asarray(lens) + 1))


def _reads_other_slots_page(monkeypatch):
    with_tables = E.ServingEngine._state_with_tables

    def tables(self, table, seq_lens):
        table = np.array(table)
        table[0, 0] = table[1, 0]
        return with_tables(self, table, seq_lens)
    monkeypatch.setattr(E.ServingEngine, "_state_with_tables", tables)


@pytest.mark.parametrize("plant", [_rope_off_by_one, _reads_other_slots_page])
def test_engine_margin_gate_catches_planted_faults(off_cpu, monkeypatch,
                                                   plant):
    """Faults in the engine's decode (rope positions off by one, one slot
    reading another's first page) trail the static path by more than the
    tolerance."""
    plant(monkeypatch)
    with pytest.raises(SystemExit, match="trails the static path"):
        serve.main(SERVE_ARGV)
