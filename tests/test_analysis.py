"""Static-analysis suite tests (docs/ANALYSIS.md).

One violating + one clean fixture per rule RA001..RA005, the suppression /
RA000 engine contract, and the CLI integration: ``python -m repro.cli lint``
must exit 0 on this repo's own tree, 1 with a structured JSON report on a
tree with an injected violation, and 2 on usage errors.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from repro.analysis import analyze_source, run_analysis

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _src(s: str) -> str:
    return textwrap.dedent(s).lstrip("\n")


def _rules(findings) -> list:
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# RA001 lock discipline
# ---------------------------------------------------------------------------

RA001_BAD = _src("""
    import threading

    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
            self._hits = 0  # guarded-by: _lock

        def bump(self):
            self._hits += 1
""")

RA001_CLEAN = _src("""
    import threading

    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
            self._hits = 0  # guarded-by: _lock

        def bump(self):
            with self._lock:
                self._hits += 1
""")


def test_ra001_unlocked_mutation_flagged():
    findings = analyze_source(RA001_BAD, rules=["RA001"])
    assert _rules(findings) == ["RA001"]
    assert findings[0].line == 9
    assert "_hits" in findings[0].message and "_lock" in findings[0].message


def test_ra001_locked_mutation_clean():
    assert analyze_source(RA001_CLEAN, rules=["RA001"]) == []


def test_ra001_guarded_dict_registry():
    src = _src("""
        class Pool:
            GUARDED = {"items": "_lock"}

            def __init__(self, lock):
                self._lock = lock
                self.items = []

            def add(self, x):
                self.items.append(x)
    """)
    findings = analyze_source(src, rules=["RA001"])
    assert _rules(findings) == ["RA001"]
    assert "items" in findings[0].message


def test_ra001_init_and_wrong_lock():
    # __init__ writes are exempt; a mutation under the WRONG lock still fires
    src = _src("""
        class C:
            def __init__(self):
                self._lock = object()
                self._other = object()
                self.n = 0  # guarded-by: _lock
                self.n = 1

            def bump(self):
                with self._other:
                    self.n += 1
    """)
    findings = analyze_source(src, rules=["RA001"])
    assert len(findings) == 1 and findings[0].line == 10


def test_ra001_mutating_method_and_subscript():
    src = _src("""
        class C:
            def __init__(self):
                self._lock = object()
                self._d = {}  # guarded-by: _lock

            def put(self, k, v):
                self._d[k] = v

            def drop(self, k):
                self._d.pop(k)
    """)
    findings = analyze_source(src, rules=["RA001"])
    assert _rules(findings) == ["RA001", "RA001"]


# ---------------------------------------------------------------------------
# RA002 tracer safety
# ---------------------------------------------------------------------------

RA002_BAD = _src("""
    import jax
    import numpy as np

    @jax.jit
    def step(x, n):
        if x:
            x = x + 1
        y = np.sum(x)
        print(y)
        return y
""")

RA002_CLEAN = _src("""
    import jax
    import jax.numpy as jnp
    from functools import partial

    @partial(jax.jit, static_argnames=("mode",))
    def step(x, mode, rng=None):
        if mode == "fast":        # static arg: fine
            x = x * 2
        if x.ndim == 3:           # attribute read: static fact
            x = x[None]
        if rng is None:           # identity vs None: no tracer bool()
            rng = jax.random.PRNGKey(0)
        return jnp.sum(x) + jax.random.uniform(rng)
""")


def test_ra002_traced_hazards_flagged():
    findings = analyze_source(RA002_BAD, rules=["RA002"])
    msgs = " | ".join(f.message for f in findings)
    assert _rules(findings).count("RA002") == 3
    assert "branch on traced value 'x'" in msgs
    assert "numpy call" in msgs
    assert "print()" in msgs


def test_ra002_static_args_attrs_and_none_identity_clean():
    assert analyze_source(RA002_CLEAN, rules=["RA002"]) == []


def test_ra002_function_passed_to_wrapper():
    src = _src("""
        import jax

        def body(carry, x):
            if carry:
                return carry, x
            return carry + 1, x

        out = jax.lax.map(body, data)
    """)
    findings = analyze_source(src, rules=["RA002"])
    assert _rules(findings) == ["RA002"]
    assert "carry" in findings[0].message


def test_ra002_bucketed_dispatch_host_loop_clean():
    """The bucketed-dispatch idiom (``sz/tiled.py::dispatch_bucketed``):
    chunk widths, slice bounds, and the pad decision are host-side ints,
    and the lambdas handed to ``jax.tree.map`` slice by those static bounds
    — none of it may trip the tracer-safety rule."""
    src = _src("""
        import jax
        import jax.numpy as jnp

        def dispatch_bucketed(fn, tree, n, widths):
            outs, off = [], 0
            for width in widths:              # host ints: static loop
                take = min(width, n - off)
                part = jax.tree.map(lambda a: a[off:off + take], tree)
                pad = width - take
                if pad:                       # host int: static branch
                    part = jax.tree.map(
                        lambda a: jnp.concatenate(
                            [a, jnp.repeat(a[:1], pad, axis=0)]), part)
                outs.append(fn(part)[:take])
                off += take
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    """)
    assert analyze_source(src, rules=["RA002"]) == []


def test_ra002_bucketed_decode_fn_traced_branch_flagged():
    """The anti-pattern the clean variant avoids: a decode fn handed to
    ``jax.lax.map`` that branches on its traced payload (say, to skip pad
    rows) would crash or silently specialize under jit — flagged."""
    src = _src("""
        import jax

        def decode_one(payload):
            if payload:
                return payload + 1
            return payload

        recon = jax.lax.map(decode_one, batch)
    """)
    findings = analyze_source(src, rules=["RA002"])
    assert _rules(findings) == ["RA002"]
    assert "payload" in findings[0].message


# ---------------------------------------------------------------------------
# RA004 exception hygiene
# ---------------------------------------------------------------------------


def test_ra004_broad_except_flagged():
    src = _src("""
        def f():
            try:
                g()
            except Exception:
                pass

        def h():
            try:
                g()
            except:
                pass
    """)
    findings = analyze_source(src, rules=["RA004"])
    assert _rules(findings) == ["RA004", "RA004"]


def test_ra004_narrow_and_cleanup_reraise_clean():
    src = _src("""
        def f():
            try:
                g()
            except ValueError:
                pass

        def h(res):
            try:
                g()
            except BaseException:
                res.close()
                raise
    """)
    assert analyze_source(src, rules=["RA004"]) == []


def test_ra004_integrity_module_raises():
    src = _src("""
        from repro.errors import CorruptContainerError

        def from_bytes(blob):
            if len(blob) < 4:
                raise ValueError("too short")
            assert blob[:4] == b"XXXX"
            return blob

        def parse_header(blob):
            raise CorruptContainerError("bad", offset=0)
    """)
    # integrity raise rules only apply inside the container modules
    findings = analyze_source(src, rules=["RA004"], rel="sz/tiled.py")
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "raises bare ValueError" in msgs and "assert" in msgs
    assert analyze_source(src, rules=["RA004"], rel="core/other.py") == []


def test_ra004_suppression_needs_reason():
    with_reason = _src("""
        def f():
            try:
                g()
            except Exception:  # lint: allow RA004 -- report harness keeps sweeping
                pass
    """)
    assert analyze_source(with_reason, rules=["RA004"]) == []
    reasonless = with_reason.replace(" -- report harness keeps sweeping", "")
    findings = analyze_source(reasonless, rules=["RA004"])
    # a reasonless annotation suppresses NOTHING: the RA004 still fires,
    # and RA000 reports the missing justification on top
    assert _rules(findings) == ["RA000", "RA004"]
    assert "reason" in findings[0].message


def test_suppression_on_line_above():
    src = _src("""
        def f():
            try:
                g()
            # lint: allow RA004 -- tolerated in this fixture
            except Exception:
                pass
    """)
    assert analyze_source(src, rules=["RA004"]) == []


# ---------------------------------------------------------------------------
# RA005 container-tag drift
# ---------------------------------------------------------------------------


def test_ra005_duplicated_tag_literals_flagged():
    src = _src("""
        MAGIC = b"GWTC"
        _VERSION = 3

        def sniff(blob):
            return blob[:4] == b"GWDS"
    """)
    findings = analyze_source(src, rules=["RA005"])
    assert _rules(findings) == ["RA005", "RA005", "RA005"]
    msgs = " | ".join(f.message for f in findings)
    assert "GWTC" in msgs and "GWDS" in msgs and "_VERSION" in msgs


def test_ra005_registry_module_and_aliases_clean():
    src = _src("""
        from repro.sz import artifact as A

        _MAGIC = A.GWTC_MAGIC
        _VERSION = A.GWTC_VERSION
        OTHER = b"OTHR"
    """)
    assert analyze_source(src, rules=["RA005"]) == []
    # literals are allowed in the registry module itself
    literal = 'GWTC_MAGIC, GWTC_VERSION = b"GWTC", 3\n'
    assert analyze_source(literal, rules=["RA005"], rel="sz/artifact.py") == []


# ---------------------------------------------------------------------------
# RA003 kernel-triple parity (project rule: needs a tree on disk)
# ---------------------------------------------------------------------------

KERNEL_MOD = _src("""
    from jax.experimental import pallas as pl

    def my_kernel_fn(x):
        return pl.pallas_call(lambda r, o: None, out_shape=x)(x)
""")


def _write_tree(root, files):
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return root


def test_ra003_complete_triple_clean(tmp_path):
    pkg = _write_tree(tmp_path / "pkg", {
        "kernels/__init__.py": "",
        "kernels/mykern.py": KERNEL_MOD,
        "kernels/ref.py": "def my_ref(x):\n    return x\n",
        "kernels/ops.py": _src("""
            from repro.kernels import ref
            from repro.kernels.mykern import my_kernel_fn

            def my_op(x, use_pallas=None):
                if use_pallas:
                    return my_kernel_fn(x)
                return ref.my_ref(x)
        """),
    })
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_k.py").write_text("def test_my_op():\n    my_op\n")
    assert run_analysis(root=pkg, rules=["RA003"], tests_dir=tests) == []


def test_ra003_missing_oracle_dispatch_and_test(tmp_path):
    pkg = _write_tree(tmp_path / "pkg", {
        "kernels/__init__.py": "",
        "kernels/mykern.py": KERNEL_MOD,
        "kernels/orphan.py": KERNEL_MOD.replace("my_kernel_fn", "orphan_fn"),
        "kernels/ref.py": "",
        "kernels/ops.py": _src("""
            from repro.kernels.mykern import my_kernel_fn

            def my_op(x, use_pallas=False):
                return my_kernel_fn(x)
        """),
    })
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_k.py").write_text("# nothing covered\n")
    findings = run_analysis(root=pkg, rules=["RA003"], tests_dir=tests)
    msgs = " | ".join(f.message for f in findings)
    assert all(f.rule == "RA003" for f in findings) and len(findings) == 4
    assert "orphan.py" in msgs                      # kernel not dispatchable
    assert "never calls a ref.* oracle" in msgs     # no reference path
    assert "use_pallas: bool | None = None" in msgs  # auto-detect contract
    assert "appears in no test" in msgs             # parity test required


def test_ra003_missing_ops_layer(tmp_path):
    pkg = _write_tree(tmp_path / "pkg", {
        "kernels/__init__.py": "",
        "kernels/mykern.py": KERNEL_MOD,
    })
    findings = run_analysis(root=pkg, rules=["RA003"])
    assert _rules(findings) == ["RA003"]
    assert "no kernels/ops.py" in findings[0].message


# ---------------------------------------------------------------------------
# engine: RA000 meta-findings, rule selection, determinism
# ---------------------------------------------------------------------------


def test_syntax_error_becomes_ra000(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    findings = run_analysis(root=tmp_path)
    assert _rules(findings) == ["RA000"]
    assert "syntax error" in findings[0].message


def test_unknown_rule_raises():
    with pytest.raises(ValueError, match="RA999"):
        run_analysis(rules=["RA999"])


def test_repo_tree_is_clean_and_fast():
    t0 = time.monotonic()
    findings = run_analysis()
    elapsed = time.monotonic() - t0
    assert findings == [], "\n".join(f.render() for f in findings)
    # single parse + walk per file keeps a full-tree lint interactive
    assert elapsed < 10.0, f"lint took {elapsed:.1f}s over src/repro"


# ---------------------------------------------------------------------------
# CLI integration: python -m repro.cli lint
# ---------------------------------------------------------------------------


def _lint(*argv, cwd=ROOT):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # the entry point turns on the persistent compile cache; tests keep it off
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return subprocess.run(
        [sys.executable, "-m", "repro.cli", "lint", *argv],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_lint_repo_clean():
    proc = _lint("--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["clean"] is True and doc["findings"] == []
    assert doc["rules"] == ["RA001", "RA002", "RA003", "RA004", "RA005"]


def test_cli_lint_violation_exits_1_with_structured_json(tmp_path):
    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "drift.py").write_text('MAGIC = b"GWTC"\n_VERSION = 3\n')
    proc = _lint("--json", "--root", str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["clean"] is False and doc["counts"] == {"RA005": 2}
    f = doc["findings"][0]
    assert f["path"] == "drift.py" and f["line"] == 1 and f["rule"] == "RA005"


def test_cli_lint_usage_errors_exit_2(tmp_path):
    assert _lint("--rule", "RA999").returncode == 2
    assert _lint("--root", str(tmp_path / "missing")).returncode == 2
    assert _lint("--write-baseline").returncode == 2


def test_cli_lint_baseline_roundtrip(tmp_path):
    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "drift.py").write_text('MAGIC = b"SZJX"\n')
    base = tmp_path / "baseline.json"
    wrote = _lint("--root", str(bad), "--baseline", str(base), "--write-baseline")
    assert wrote.returncode == 0 and base.is_file()
    accepted = _lint("--root", str(bad), "--baseline", str(base))
    assert accepted.returncode == 0, accepted.stdout + accepted.stderr
    # without the baseline the same tree still fails
    assert _lint("--root", str(bad)).returncode == 1
