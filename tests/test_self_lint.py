"""AST self-lint (analysis/ast_rules.py): the package gate — paddle_tpu's
own source plus bench.py must produce zero findings — and per-rule mutation
fixtures proving each rule fires.  Also covers the flags satellite: the
define_flag re-registration guard (runtime twin of rule A204)."""

import os
import textwrap

import pytest

from paddle_tpu.analysis import format_diagnostics, lint_file, lint_package

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules(diags):
    return [d.rule for d in diags]


def _lint_src(tmp_path, src, relname="reader/mod.py"):
    p = tmp_path / relname
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return lint_file(str(p), root=str(tmp_path))


# ---------------------------------------------------------------------------
# the repo gate: our own source is clean
# ---------------------------------------------------------------------------


def test_package_self_lint_is_clean():
    diags = lint_package(
        extra_paths=[os.path.join(REPO, "bench.py")]
    )
    assert diags == [], format_diagnostics(diags)


# ---------------------------------------------------------------------------
# mutation fixtures
# ---------------------------------------------------------------------------


def test_a201_time_in_jitted_function(tmp_path):
    d = _lint_src(tmp_path, """
        import time
        import jax

        @jax.jit
        def step(x):
            return x * time.time()
    """, "mod.py")
    assert rules(d) == ["A201"]
    assert d[0].line == 7 and d[0].hint


def test_a201_via_jit_call_by_name(tmp_path):
    d = _lint_src(tmp_path, """
        import time
        import jax

        def make_step():
            def step(x):
                return x + time.perf_counter()
            return jax.jit(step, donate_argnums=(0,))
    """, "mod.py")
    assert rules(d) == ["A201"]


def test_a201_partial_jit_decorator(tmp_path):
    d = _lint_src(tmp_path, """
        import functools
        import time
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def step(x, n):
            return x * time.monotonic()
    """, "mod.py")
    assert rules(d) == ["A201"]


def test_a202_host_rng_in_jitted_function(tmp_path):
    d = _lint_src(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + np.random.rand()
    """, "mod.py")
    assert rules(d) == ["A202"]


def test_a202_jitted_lambda(tmp_path):
    d = _lint_src(tmp_path, """
        import jax
        import random

        fn = jax.jit(lambda x: x * random.random())
    """, "mod.py")
    assert rules(d) == ["A202"]


def test_unjitted_time_and_rng_are_fine(tmp_path):
    d = _lint_src(tmp_path, """
        import time
        import numpy as np

        def host_loop(x):
            t0 = time.time()
            return x + np.random.rand(), time.time() - t0
    """, "mod.py")
    assert d == []


def test_a203_global_rng_in_reader_module(tmp_path):
    d = _lint_src(tmp_path, """
        import random

        def reader():
            data = list(range(10))
            random.shuffle(data)
            yield from data
    """, "reader/creator2.py")
    assert rules(d) == ["A203"]


def test_a203_seeded_rng_is_fine(tmp_path):
    d = _lint_src(tmp_path, """
        import random
        import numpy as np

        def reader(seed=0):
            rng = random.Random(seed)
            nrng = np.random.RandomState(seed)
            data = list(range(10))
            rng.shuffle(data)
            yield from (data + [nrng.rand()])
    """, "dataset/gen.py")
    assert d == []


def test_a203_not_applied_outside_reader_modules(tmp_path):
    d = _lint_src(tmp_path, """
        import random

        def sample():
            return random.random()
    """, "models/gen.py")
    assert d == []


def test_a206_pickle_loads_flagged(tmp_path):
    d = _lint_src(tmp_path, """
        import pickle

        def decode(blob):
            return pickle.loads(blob)
    """, "mod.py")
    assert rules(d) == ["A206"]
    assert "master_wire" in d[0].message and d[0].hint


def test_a206_alias_and_from_import(tmp_path):
    d = _lint_src(tmp_path, """
        import pickle as pkl
        from pickle import loads as unmarshal

        def a(b):
            return pkl.load(b), unmarshal(b), pkl.Unpickler(b)
    """, "mod.py")
    assert rules(d) == ["A206", "A206", "A206"]


def test_a206_bare_conn_recv_flagged_socket_recv_fine(tmp_path):
    d = _lint_src(tmp_path, """
        def pump(conn, sock):
            msg = conn.recv()          # Connection-style: implicit unpickle
            raw = sock.recv(4096)      # socket-style bytes read: fine
            return msg, raw
    """, "mod.py")
    assert rules(d) == ["A206"]
    assert d[0].line == 3


def test_a206_dumps_and_master_wire_exempt(tmp_path):
    # serializing is legal everywhere; deserializing is legal in the codec
    d = _lint_src(tmp_path, """
        import pickle

        def save(obj, f):
            pickle.dump(obj, f)
            return pickle.dumps(obj)
    """, "mod.py")
    assert d == []
    d = _lint_src(tmp_path, """
        import pickle

        def decode(blob):
            return pickle.loads(blob)
    """, "paddle_tpu/master_wire.py")
    assert d == []


def test_a206_pragma_suppresses_with_justification(tmp_path):
    d = _lint_src(tmp_path, """
        import pickle

        def decode(blob):
            return pickle.loads(blob)  # wire: allow[A206] local md5-verified dataset file
    """, "mod.py")
    assert d == []


def test_a206_empty_pragma_justification_rejected(tmp_path):
    d = _lint_src(tmp_path, """
        import pickle

        def decode(blob):
            return pickle.loads(blob)  # wire: allow[A206]
    """, "mod.py")
    # the malformed pragma reports (and the hazard is NOT double-reported)
    assert rules(d) == ["A206"]
    assert "justification" in d[0].message


def test_a206_stale_pragma_flagged(tmp_path):
    d = _lint_src(tmp_path, """
        def harmless():  # wire: allow[A206] nothing here needs this anymore
            return 1
    """, "mod.py")
    assert rules(d) == ["A206"]
    assert "unused" in d[0].message


def test_a204_duplicate_flag_definition(tmp_path):
    a = tmp_path / "pkg" / "flags_a.py"
    b = tmp_path / "pkg" / "flags_b.py"
    a.parent.mkdir(parents=True)
    a.write_text('define_flag("seed", 0, "x")\n')
    b.write_text('define_flag("seed", 1, "y")\n')
    defs = {}
    d = lint_file(str(a), root=str(tmp_path), _flag_defs=defs)
    d += lint_file(str(b), root=str(tmp_path), _flag_defs=defs)
    assert rules(d) == ["A204"]
    assert "flags_a.py" in d[0].message  # provenance of the first definition


# ---------------------------------------------------------------------------
# flags satellite: runtime re-registration guard
# ---------------------------------------------------------------------------


def test_define_flag_identical_reregistration_is_noop():
    from paddle_tpu.utils import flags

    flags.define_flag("_test_lint_flag", 7, "probe")
    try:
        flags.define_flag("_test_lint_flag", 7, "probe again")  # no raise
        assert flags.get_flag("_test_lint_flag") == 7
    finally:
        flags._DEFS.pop("_test_lint_flag", None)


def test_define_flag_conflicting_reregistration_raises():
    from paddle_tpu.utils import flags

    flags.define_flag("_test_lint_flag2", 7, "probe")
    try:
        with pytest.raises(ValueError, match="already defined"):
            flags.define_flag("_test_lint_flag2", 8, "conflicting default")
        with pytest.raises(ValueError, match="already defined"):
            flags.define_flag("_test_lint_flag2", "7", "conflicting type")
        # the original definition survives the failed re-registration
        assert flags.get_flag("_test_lint_flag2") == 7
    finally:
        flags._DEFS.pop("_test_lint_flag2", None)


# ---------------------------------------------------------------------------
# CLI face
# ---------------------------------------------------------------------------


def test_cli_lint_self_clean():
    from paddle_tpu.cli import main

    assert main(["lint"]) == 0


def test_cli_lint_reports_bad_config(tmp_path, capsys):
    cfg = tmp_path / "bad_conf.py"
    cfg.write_text(textwrap.dedent("""
        from paddle.trainer_config_helpers import *
        settings(batch_size=8, learning_rate=1e-3)
        x = data_layer(name="x", size=8)
        a = fc_layer(input=x, size=8, name="a")
        b = fc_layer(input=x, size=12, name="b")
        s = addto_layer(input=[a, b], name="sum")
        outputs(s)
    """))
    from paddle_tpu.cli import main

    rc = main(["lint", f"--config={cfg}"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "G004" in out and "'sum'" in out and "fix:" in out


# ---------------------------------------------------------------------------
# tier-1 failure-set snapshot tooling
# ---------------------------------------------------------------------------


def test_tier1_failset_parses_summary_lines():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tier1_failset", os.path.join(REPO, "scripts", "tier1_failset.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    log = textwrap.dedent("""
        ....F..E
        =========================== short test summary info ====================
        FAILED tests/test_a.py::test_one - AssertionError: boom
        FAILED tests/test_a.py::test_two[case - with - dashes]
        ERROR tests/test_b.py::test_three
        1 failed, 1 passed in 0.1s
    """)
    got = mod.parse_failures(log)
    assert got == {
        "tests/test_a.py::test_one",
        "tests/test_a.py::test_two[case - with - dashes]",
        "tests/test_b.py::test_three",
    }
    # the committed baseline (empty when tier-1 is clean) matches the
    # parser's id format
    assert all("::" in t for t in mod.load_baseline())


def test_a202_jax_random_from_import_not_flagged(tmp_path):
    """Review regression: `from jax import random` is the jit-SAFE jax
    namespace; only the stdlib `import random` binding may flag."""
    d = _lint_src(tmp_path, """
        import jax
        from jax import random

        @jax.jit
        def step(key, x):
            return x + random.normal(key, x.shape)
    """, "mod.py")
    assert d == []


def test_cli_lint_multiple_configs_one_process(tmp_path, capsys):
    good = tmp_path / "good.py"
    good.write_text(textwrap.dedent("""
        from paddle.trainer_config_helpers import *
        settings(batch_size=8, learning_rate=1e-3)
        x = data_layer(name="x", size=8)
        outputs(fc_layer(input=x, size=4, name="out"))
    """))
    dup = tmp_path / "dup.py"
    dup.write_text(textwrap.dedent("""
        from paddle.trainer_config_helpers import *
        settings(batch_size=8, learning_rate=1e-3)
        x = data_layer(name="x", size=8)
        a = fc_layer(input=x, size=4, name="twin")
        b = fc_layer(input=a, size=8, name="twin")
        outputs(b)
    """))
    from paddle_tpu.cli import main

    assert main(["lint", f"--config={good}"]) == 0
    # a config whose BUILD raises reports formatted diagnostics, not a
    # traceback, and rides alongside other configs in one process
    rc = main(["lint", f"--config={good}", f"--config={dup}"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "G016" in out and "'twin'" in out and "fix:" in out


def test_a201_jit_by_name_is_scope_aware(tmp_path):
    """Review regression: two factories each define a local `step`; only one
    is jitted.  The host-side step's time call must NOT flag."""
    d = _lint_src(tmp_path, """
        import time
        import jax

        def jitted_factory():
            def step(x):
                return x * 2
            return jax.jit(step)

        def host_factory():
            def step(x):
                return x, time.perf_counter()
            return step
    """, "mod.py")
    assert d == []


def test_tier1_failset_ignores_captured_log_errors():
    """Review regression: 'ERROR ...' log records captured in test output
    must not be parsed as failing node ids — only the short-summary
    section counts."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tier1_failset2", os.path.join(REPO, "scripts", "tier1_failset.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    log = textwrap.dedent("""
        ------------------------------ Captured log call ----------------------
        ERROR    root:provider.py:12 could not fetch dataset
        FAILED to connect to pserver (retrying)
        =========================== short test summary info ====================
        FAILED tests/test_a.py::test_one - RuntimeError
        1 failed in 0.1s
    """)
    assert mod.parse_failures(log) == {"tests/test_a.py::test_one"}
