"""Device policy (``repro.device``): interpret-or-compile, the counted
pallas->numpy downgrade, and where the persistent compile cache lives.

These run on the CPU; the TPU side of each decision is reached by
steering ``device.platform`` from the test.
"""

import os
import pathlib
import subprocess
import sys
import uuid

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import device, telemetry  # noqa: E402
from repro.core import ColumnSpec, CompressedTable, TableCodec  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]

SCHEMA = [
    ColumnSpec("id", "int"),
    ColumnSpec("city", "cat"),
    ColumnSpec("qty", "int"),
    ColumnSpec("amount", "float", precision=0.01),
]


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{
        "id": int(i),
        "city": f"City{int(rng.zipf(1.3)) % 20:02d}",
        "qty": int(rng.integers(1, 100)),
        "amount": float(np.round(rng.uniform(0.01, 999.99), 2)),
    } for i in range(n)]


@pytest.fixture
def on_platform(monkeypatch):
    """Make the policy see ``name`` as JAX's platform for one test."""

    def steer(name):
        monkeypatch.setattr(device, "platform", lambda: name)
        device.interpret_default.cache_clear()

    yield steer
    device.interpret_default.cache_clear()


class TestInterpretDecision:
    def test_cpu_interprets(self):
        assert jax.default_backend() == "cpu"
        assert device.interpret_default() is True

    def test_tpu_compiles(self, on_platform):
        on_platform("tpu")
        assert device.interpret_default() is False

    def test_interpreter_refused_on_tpu(self):
        """The kernel's entry point does not take ``interpret`` from its
        caller, so nothing can ask for the interpreter on a TPU: the flag
        comes from the platform policy alone."""
        import inspect

        from repro.kernels import delayed_decode as dd

        params = inspect.signature(dd.delayed_decode_runs).parameters
        assert "interpret" not in params
        assert "device.interpret_default()" in inspect.getsource(dd)

    def test_engine_decode_takes_the_platform_choice(self, on_platform,
                                                     monkeypatch):
        """The engine's kernel call passes no ``interpret``: the policy
        decides, so a TPU run compiles the kernel."""
        from repro.kernels import delayed_decode as dd

        seen = []
        real = dd._delayed_decode_jit

        def spy(codes, runs, tables, m_bits, interpret):
            seen.append(interpret)
            return real(codes, runs, tables, m_bits, True)

        monkeypatch.setattr(dd, "_delayed_decode_jit", spy)
        rows = _rows(300)
        table = CompressedTable(TableCodec.fit(rows, SCHEMA, sample=256))
        table.extend(rows)
        table.flush()
        idx = np.arange(0, 300, 7)
        want = table.get_many(idx, backend="numpy")
        assert table.get_many(idx, backend="pallas") == want
        on_platform("tpu")
        assert table.get_many(idx, backend="pallas") == want
        assert seen == [True, False]


class TestDowngradeCounter:
    def test_counted_when_plan_cannot_run_the_kernel(self):
        rows = _rows(400, seed=1)
        codec = TableCodec.fit(rows, SCHEMA, sample=256)
        table = CompressedTable(codec, use_pallas=True)
        table.extend(rows)
        table.flush()
        plan = codec.compile()
        assert plan.pallas_ok
        ctr = telemetry.counter("repro.plan.pallas_downgrade")
        idx = np.arange(0, 400, 3)
        want = table.get_many(idx, backend="numpy")
        n0 = ctr.value
        assert table.get_many(idx) == want        # use_pallas=True: kernel
        assert table.get_many(idx, backend="pallas") == want
        assert ctr.value == n0
        plan.pallas_ok = False                    # e.g. conditional slots
        assert table.get_many(idx) == want        # use_pallas=True
        assert table.get_many(idx, backend="pallas") == want
        assert table.get_many(idx, backend="numpy") == want  # not a downgrade
        assert ctr.value == n0 + 2
        assert table._resolve_backend("pallas", 10) == "numpy"
        assert ctr.value == n0 + 3

    def test_catalogued(self):
        from repro.telemetry.catalog import METRICS

        assert "repro.plan.pallas_downgrade" in METRICS


# (backend argument, store's use_pallas, plan can run the kernel) ->
# (decode backend, counted as a pallas->numpy downgrade), on the CPU.
# The auto row (None, None, True) is numpy here because the CPU
# interprets; TestBackendDecisionTable.test_auto_mode_on_tpu takes it
# on a TPU.
DECISIONS = {
    (None, None, True): ("numpy", False),
    (None, None, False): ("numpy", False),
    (None, True, True): ("pallas", False),
    (None, True, False): ("numpy", True),
    (None, False, True): ("numpy", False),
    (None, False, False): ("numpy", False),
    ("numpy", None, True): ("numpy", False),
    ("numpy", None, False): ("numpy", False),
    ("numpy", True, True): ("numpy", False),
    ("numpy", True, False): ("numpy", False),
    ("numpy", False, True): ("numpy", False),
    ("numpy", False, False): ("numpy", False),
    ("pallas", None, True): ("pallas", False),
    ("pallas", None, False): ("numpy", True),
    ("pallas", True, True): ("pallas", False),
    ("pallas", True, False): ("numpy", True),
    ("pallas", False, True): ("pallas", False),
    ("pallas", False, False): ("numpy", True),
}


class TestBackendDecisionTable:
    """Host or device is decided today by three inputs: the ``backend``
    argument, the store's ``use_pallas`` and the auto mode's
    ``PALLAS_MIN_ROWS`` on a TPU.  ``CompressedTable._resolve_backend``
    combines them; these cases pin what it returns, so that folding the
    three into one policy changes this table on purpose only."""

    @pytest.fixture(scope="class")
    def codec(self):
        return TableCodec.fit(_rows(400, seed=2), SCHEMA, sample=256)

    @pytest.mark.parametrize("eligible", [True, False],
                             ids=["eligible", "conditional"])
    @pytest.mark.parametrize("use_pallas", [None, True, False],
                             ids=["store_auto", "store_pallas", "store_numpy"])
    @pytest.mark.parametrize("backend", [None, "numpy", "pallas"],
                             ids=["arg_none", "arg_numpy", "arg_pallas"])
    def test_decision(self, codec, monkeypatch, backend, use_pallas,
                      eligible):
        plan = codec.compile()
        assert plan.pallas_ok
        if not eligible:
            # a plan with conditional slots cannot run the kernel
            monkeypatch.setattr(plan, "pallas_ok", False)
        table = CompressedTable(codec, use_pallas=use_pallas)
        ctr = telemetry.counter("repro.plan.pallas_downgrade")
        n0 = ctr.value
        got = table._resolve_backend(backend, CompressedTable.PALLAS_MIN_ROWS)
        want, counted = DECISIONS[(backend, use_pallas, eligible)]
        assert got == want
        assert ctr.value == n0 + int(counted)

    @pytest.mark.parametrize("below", [True, False],
                             ids=["below_min_rows", "at_min_rows"])
    def test_auto_mode_on_tpu(self, codec, on_platform, below):
        """With no argument and no store choice, a TPU takes the kernel
        from ``PALLAS_MIN_ROWS`` rows on."""
        assert codec.compile().pallas_ok
        on_platform("tpu")
        table = CompressedTable(codec)
        n = CompressedTable.PALLAS_MIN_ROWS - int(below)
        assert table._resolve_backend(None, n) == ("numpy" if below
                                                   else "pallas")


def _restore_cache_dir(prev):
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()
    device.configure_compile_cache.cache_clear()


class TestCompileCacheDir:
    def test_env_var_is_honoured(self, monkeypatch, tmp_path):
        monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
        assert device.compile_cache_dir() == str(tmp_path)

    def test_default_is_fixed_inside_checkout(self, monkeypatch):
        monkeypatch.delenv(device.CACHE_ENV, raising=False)
        assert device.compile_cache_dir() == str(REPO / ".jax_cache")
        assert device.compile_cache_dir() == str(device.CHECKOUT_CACHE_DIR)
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored

    def test_configure_points_jax_at_the_dir(self, monkeypatch, tmp_path):
        prev = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
        device.configure_compile_cache.cache_clear()
        try:
            assert device.configure_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        finally:
            _restore_cache_dir(prev)

    def test_set_env_var_is_left_alone(self, monkeypatch, tmp_path):
        """With the variable set (JAX reads it itself), nothing is set."""
        prev = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        device.configure_compile_cache.cache_clear()
        try:
            assert device.configure_compile_cache() == str(tmp_path)
            assert updates == []
        finally:
            monkeypatch.undo()
            _restore_cache_dir(prev)

    @pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
    def test_compile_lands_in_the_cache_dir(self, tmp_path, env_set):
        """A compile in a fresh CPU process writes its entry to the
        variable's directory when set, else to the checkout's."""
        probe = f"cache_probe_{uuid.uuid4().hex[:12]}"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=str(REPO / "src"))
        env.pop(device.CACHE_ENV, None)
        where = device.CHECKOUT_CACHE_DIR
        if env_set:
            env[device.CACHE_ENV] = str(tmp_path)
            where = tmp_path
        code = (
            "from repro import device\n"
            "device.configure_compile_cache()\n"
            "import jax\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
            f"def {probe}(x):\n"
            "    return x * 3.25 + 1\n"
            f"jax.jit({probe})(1.0).block_until_ready()\n"
        )
        subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       timeout=120)
        written = [p.name for p in pathlib.Path(where).iterdir()
                   if p.name.startswith(f"jit_{probe}")]
        assert written
        for p in written:
            (pathlib.Path(where) / p).unlink()
