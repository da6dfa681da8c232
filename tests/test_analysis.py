"""slt-lint (PR 6): the static rules on per-rule fixtures (positive,
negative, waiver), the SLT002 CFG on try/finally and early-return
shapes, the engine's exit-code contract, the spans-registry drift
guards, and the obs/locks.py watchdog (intentional inversion detected;
watchdog-off locks are plain threading primitives and the training
numerics are bit-identical either way)."""

import ast
import textwrap
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from split_learning_tpu.analysis import cfg as cfg_mod
from split_learning_tpu.analysis import engine
from split_learning_tpu.obs import dispatch_debug
from split_learning_tpu.obs import locks, spans
from split_learning_tpu.obs import trace as obs_trace
from split_learning_tpu.obs.metrics import Registry

REPO = Path(__file__).resolve().parent.parent


def _lint(tmp_path, relpath, source):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return engine.lint_file(str(p))


def _rules(findings, *, waived=None):
    return sorted(f.rule for f in findings
                  if waived is None or f.waived is waived)


# ---------------------------------------------------------------------- #
# SLT001: D2H / blocking under the lock
# ---------------------------------------------------------------------- #

def test_slt001_flags_d2h_under_lock(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        import numpy as np
        class ServerRuntime:
            def step(self):
                with self._lock:
                    g = np.asarray(self.dev)
                    loss = float(self.loss_dev)
                    self.fut.result()
                return g, loss
    """)
    assert _rules(findings) == ["SLT001", "SLT001", "SLT001"]


def test_slt001_negative_shapes(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        import numpy as np
        import jax.numpy as jnp
        class ServerRuntime:
            def step(self):
                with self._lock:
                    acts = jnp.asarray(self.host)   # H2D: allowed
                g = np.asarray(self.dev)            # off-lock
                return g
            def wait_ok(self):
                with self._cond:
                    while not self.ready:
                        self._cond.wait(timeout=1.0)  # the held cond itself
        class _GroupD2H:
            def _materialize(self):
                with self._lock:                    # the D2H latch
                    self.g = np.asarray(self._g_dev)
    """)
    assert findings == []


def test_slt001_flags_d2h_in_a_gated_branch(tmp_path):
    """No attribute test exempts a branch: a host copy under the lock is
    a finding whatever ``if`` it sits in, on either arm."""
    findings = _lint(tmp_path, "runtime/server.py", """
        import numpy as np
        class ServerRuntime:
            def step(self):
                with self._lock:
                    if not self.overlap:
                        g = np.asarray(self.dev)
                    if self.overlap:
                        pass
                    else:
                        g = np.asarray(self.dev)
                return g
    """)
    assert _rules(findings) == ["SLT001", "SLT001"]


def test_slt001_out_of_scope_dir(tmp_path):
    findings = _lint(tmp_path, "models/thing.py", """
        import numpy as np
        class M:
            def f(self):
                with self._lock:
                    return np.asarray(self.dev)
    """)
    assert findings == []


def test_slt001_inline_waiver_same_line_and_line_above(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        import numpy as np
        class ServerRuntime:
            def a(self):
                with self._lock:
                    g = np.asarray(self.dev)  # slt-lint: disable=SLT001 (demo)
                return g
            def b(self):
                with self._lock:
                    # slt-lint: disable=SLT001 (next-line demo)
                    g = np.asarray(self.dev)
                return g
    """)
    assert _rules(findings, waived=True) == ["SLT001", "SLT001"]
    assert _rules(findings, waived=False) == []


def test_waiver_without_reason_is_itself_a_finding(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        import numpy as np
        class ServerRuntime:
            def a(self):
                with self._lock:
                    g = np.asarray(self.dev)  # slt-lint: disable=SLT001 ()
                return g
    """)
    rules = _rules(findings, waived=False)
    assert "SLT000" in rules and "SLT001" in rules  # waiver void, both red


# ---------------------------------------------------------------------- #
# SLT002: claim pairing through the CFG
# ---------------------------------------------------------------------- #

def test_slt002_early_return_leaks_claim(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        class S:
            def step(self, step):
                entry, owner = self.replay.begin(0, "op", step)
                if not owner:
                    return self.replay.wait(entry)
                res = self.compute()
                if res is None:
                    return None
                self.replay.resolve(entry, res)
                return res
    """)
    assert _rules(findings) == ["SLT002"]


def test_slt002_try_except_pairing_is_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        class S:
            def step(self, step):
                entry, owner = self.replay.begin(0, "op", step)
                if not owner:
                    return self.replay.wait(entry)
                try:
                    res = self.compute()
                    if entry is not None:
                        self.replay.resolve(entry, res)
                    return res
                except BaseException as exc:
                    if entry is not None:
                        self.replay.fail(entry, exc)
                    raise
    """)
    assert findings == []


def test_slt002_resolve_in_finally_is_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        class S:
            def step(self, step):
                entry, owner = self.replay.begin(0, "op", step)
                try:
                    return self.compute()
                finally:
                    self.replay.resolve(entry, None)
    """)
    assert findings == []


def test_slt002_finally_without_resolve_leaks(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        class S:
            def step(self, step):
                entry, owner = self.replay.begin(0, "op", step)
                try:
                    return self.compute()
                finally:
                    self.log("done")
    """)
    assert _rules(findings) == ["SLT002"]


def test_slt002_typed_handler_can_leak_past_handlers(tmp_path):
    # a KeyError handler does not catch a RuntimeError: the exceptional
    # edge escapes the try and the claim leaks
    findings = _lint(tmp_path, "runtime/server.py", """
        class S:
            def step(self, step):
                entry, owner = self.replay.begin(0, "op", step)
                try:
                    res = self.compute()
                except KeyError as exc:
                    self.replay.fail(entry, exc)
                    raise
                self.replay.resolve(entry, res)
                return res
    """)
    assert _rules(findings) == ["SLT002"]


def test_cfg_routes_return_through_finally():
    fn = ast.parse(textwrap.dedent("""
        def f(self):
            try:
                return self.work()
            finally:
                self.cleanup()
    """)).body[0]
    graph = cfg_mod.build(fn)
    ret = next(n for n in graph.nodes if isinstance(n.stmt, ast.Return))
    # the return's successor is a duplicated finally statement, not EXIT
    succs = [t for t, _c in ret.succs]
    assert graph.exit not in succs
    assert any(isinstance(t.stmt, ast.Expr) for t in succs)


def test_cfg_early_return_reaches_exit_directly():
    fn = ast.parse(textwrap.dedent("""
        def f(self, x):
            if x is None:
                return 0
            return 1
    """)).body[0]
    graph = cfg_mod.build(fn)
    returns = [n for n in graph.nodes if isinstance(n.stmt, ast.Return)]
    assert len(returns) == 2
    for r in returns:
        assert graph.exit in [t for t, _c in r.succs]


# ---------------------------------------------------------------------- #
# SLT003: span literals
# ---------------------------------------------------------------------- #

def test_slt003_flags_literal_and_accepts_constant(tmp_path):
    findings = _lint(tmp_path, "runtime/worker.py", """
        from split_learning_tpu.obs import spans
        def go(tr, stats, reg, dt):
            tr.record("client_fwd", 0.0, dt)
            stats.record_span("wire", dt)
            reg.observe("lock_hold", dt)
            tr.record(spans.CLIENT_FWD, 0.0, dt)
            stats.record(dt)
    """)
    assert _rules(findings) == ["SLT003", "SLT003", "SLT003"]


def test_slt003_flags_literal_names_at_the_span_primitive(tmp_path):
    findings = _lint(tmp_path, "runtime/worker.py", """
        from split_learning_tpu import obs
        from split_learning_tpu.obs import spans
        def go(t0, t1):
            with obs.span("client_fwd"):
                pass
            obs.span_at("queue_wait", t0, t1)
            with obs.span(spans.CLIENT_FWD, bytes=3):
                pass
            obs.span_at(spans.QUEUE_WAIT, t0, t1)
    """)
    assert _rules(findings) == ["SLT003", "SLT003"]


def test_slt003_waiver(tmp_path):
    findings = _lint(tmp_path, "runtime/worker.py", """
        def go(tr, dt):
            tr.record("legacy", 0.0, dt)  # slt-lint: disable=SLT003 (old export)
    """)
    assert _rules(findings, waived=True) == ["SLT003"]
    assert _rules(findings, waived=False) == []


# ---------------------------------------------------------------------- #
# SLT004: wire-path determinism
# ---------------------------------------------------------------------- #

def test_slt004_flags_global_rng_unseeded_ctor_and_wall_clock(tmp_path):
    findings = _lint(tmp_path, "ops/noise.py", """
        import random
        import time
        import numpy as np
        def draw():
            a = random.random()
            rs = np.random.RandomState()
            b = np.random.rand(3)
            t = time.time()
            return a, rs, b, t
    """)
    assert _rules(findings) == ["SLT004"] * 4


def test_slt004_seeded_and_measurement_clocks_are_clean(tmp_path):
    findings = _lint(tmp_path, "transport/chaos.py", """
        import random
        import time
        import numpy as np
        def draw(seed):
            rng = random.Random(seed)
            rs = np.random.RandomState(seed & 0x7FFFFFFF)
            t0 = time.perf_counter()
            time.sleep(0.0)
            return rng.random(), rs.rand(), time.monotonic() - t0
    """)
    assert findings == []


def test_slt004_flags_nondet_import(tmp_path):
    findings = _lint(tmp_path, "transport/codec.py", """
        from random import shuffle
    """)
    assert _rules(findings) == ["SLT004"]


def test_slt004_out_of_scope(tmp_path):
    findings = _lint(tmp_path, "launch/cli.py", """
        import time
        def now():
            return time.time()
    """)
    assert findings == []


# ---------------------------------------------------------------------- #
# SLT005: lock-order cycles
# ---------------------------------------------------------------------- #

def test_slt005_direct_cycle(tmp_path):
    findings = _lint(tmp_path, "runtime/sharded.py", """
        class S:
            def a(self):
                with self._alpha_lock:
                    with self._beta_lock:
                        pass
            def b(self):
                with self._beta_lock:
                    with self._alpha_lock:
                        pass
    """)
    assert _rules(findings) == ["SLT005"]


def test_slt005_transitive_cycle_through_method_call(tmp_path):
    findings = _lint(tmp_path, "runtime/sharded.py", """
        class S:
            def outer(self):
                with self._alpha_lock:
                    self.inner()
            def inner(self):
                with self._beta_lock:
                    pass
            def rev(self):
                with self._beta_lock:
                    with self._alpha_lock:
                        pass
    """)
    assert _rules(findings) == ["SLT005"]


def test_slt005_consistent_order_is_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/sharded.py", """
        class S:
            def a(self):
                with self._alpha_lock:
                    with self._beta_lock:
                        pass
            def b(self):
                with self._alpha_lock:
                    with self._beta_lock:
                        pass
    """)
    assert findings == []


# ---------------------------------------------------------------------- #
# SLT006: use-after-donate
# ---------------------------------------------------------------------- #

def test_slt006_read_after_donate(tmp_path):
    findings = _lint(tmp_path, "runtime/trainer.py", """
        import jax
        class T:
            def __init__(self, step_fn):
                self._step = jax.jit(step_fn, donate_argnums=(0,))
            def train(self, state, x):
                new_state, loss = self._step(state, x)
                norm = state.norm()
                return new_state, loss, norm
    """)
    assert _rules(findings) == ["SLT006"]
    assert "donate_argnums" in findings[0].message


def test_slt006_rebind_over_donation_is_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/trainer.py", """
        import jax
        class T:
            def __init__(self, step_fn):
                self._step = jax.jit(step_fn, donate_argnums=(0,))
            def train(self, state, x):
                state, loss = self._step(state, x)
                return state.norm(), loss
            def fresh_then_read(self, state, x):
                new_state, loss = self._step(state, x)
                state = new_state
                return state.norm(), loss
    """)
    assert findings == []


def test_slt006_inline_waiver(tmp_path):
    findings = _lint(tmp_path, "runtime/trainer.py", """
        import jax
        class T:
            def __init__(self, step_fn):
                self._step = jax.jit(step_fn, donate_argnums=(0,))
            def train(self, state, x):
                new_state, loss = self._step(state, x)
                norm = state.norm()  # slt-lint: disable=SLT006 (demo)
                return new_state, loss, norm
    """)
    assert _rules(findings, waived=True) == ["SLT006"]
    assert _rules(findings, waived=False) == []


# ---------------------------------------------------------------------- #
# SLT007: retrace hazards
# ---------------------------------------------------------------------- #

def test_slt007_jit_closure_over_mutable_self_attr(tmp_path):
    findings = _lint(tmp_path, "runtime/trainer.py", """
        import jax
        class T:
            def __init__(self):
                def step(x):
                    return x * self._scale
                self._step = jax.jit(step)
            def set_scale(self, s):
                self._scale = s
    """)
    assert _rules(findings) == ["SLT007"]
    assert "_scale" in findings[0].message


def test_slt007_varying_literals_and_nonhashable_static(tmp_path):
    findings = _lint(tmp_path, "ops/kern.py", """
        import jax
        def f(x, n):
            return x * n
        _g = jax.jit(f)
        _h = jax.jit(f, static_argnums=(1,))
        def a(x):
            return _g(x, 2)
        def b(x):
            return _g(x, 3)
        def c(x):
            return _h(x, 2)
        def d(x):
            return _h(x, 3)
        def e(x):
            return _h(x, [1, 2])
    """)
    # _g varies a traced literal; _h's variation is static (fine) but
    # the list literal at a static position is non-hashable
    assert _rules(findings) == ["SLT007", "SLT007"]


def test_slt007_immutable_attr_and_same_literal_are_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/trainer.py", """
        import jax
        class T:
            def __init__(self, lr):
                self._lr = lr
                def step(x):
                    return x * self._lr
                self._step = jax.jit(step)
            def go(self, x):
                return self._step(x)
    """)
    assert findings == []


# ---------------------------------------------------------------------- #
# SLT008: implicit host sync on traced values
# ---------------------------------------------------------------------- #

def test_slt008_branch_bool_and_scalar_before_bulk(tmp_path):
    findings = _lint(tmp_path, "runtime/worker.py", """
        import jax
        import numpy as np
        def step_fn(x):
            return x
        _step = jax.jit(step_fn)
        class R:
            def brancher(self, x):
                loss = _step(x)
                if loss:
                    return 0.0
                return 1.0
            def boolsync(self, x):
                loss = _step(x)
                return bool(loss)
            def eager_scalar(self, x):
                g, loss = _step(x)
                l = float(loss)
                gh = np.asarray(g)
                return gh, l
    """)
    assert _rules(findings) == ["SLT008", "SLT008", "SLT008"]


def test_slt008_bulk_first_and_lone_scalar_are_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/worker.py", """
        import jax
        import numpy as np
        def step_fn(x):
            return x
        _step = jax.jit(step_fn)
        class R:
            def drained(self, x):
                g, loss = _step(x)
                gh = np.asarray(g)
                return gh, float(loss)
            def lone_scalar(self, x):
                loss = _step(x)
                return float(loss)
            def host_if(self, x):
                loss = _step(x)
                loss = float(loss)
                if loss:
                    return 0.0
                return loss
    """)
    assert findings == []


# ---------------------------------------------------------------------- #
# SLT009: PRNG key discipline
# ---------------------------------------------------------------------- #

def test_slt009_double_consumption_and_loop_reuse(tmp_path):
    findings = _lint(tmp_path, "ops/noise.py", """
        import jax
        def double(key, shape):
            a = jax.random.normal(key, shape)
            b = jax.random.uniform(key, shape)
            return a + b
        def loopy(key, xs):
            out = 0.0
            for x in xs:
                out = out + jax.random.normal(key, x.shape)
            return out
    """)
    assert _rules(findings) == ["SLT009", "SLT009"]


def test_slt009_split_and_fold_in_are_clean(tmp_path):
    findings = _lint(tmp_path, "ops/noise.py", """
        import jax
        def ok(key, shape):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, shape)
            b = jax.random.normal(k2, shape)
            return a + b
        def per_step(key, xs):
            out = 0.0
            for i, x in enumerate(xs):
                k = jax.random.fold_in(key, i)
                out = out + jax.random.normal(k, x.shape)
            return out
    """)
    assert findings == []


# ---------------------------------------------------------------------- #
# SLT010: wire-schema contract (project rule, cross-file)
# ---------------------------------------------------------------------- #

def _lint_tree(tmp_path, files, waiver_text=None):
    for rel, srctext in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(srctext))
    wf = None
    if waiver_text is not None:
        wfp = tmp_path / "waivers"
        wfp.write_text(waiver_text)
        wf = str(wfp)
    return engine.lint_paths([str(tmp_path)], waiver_file=wf)


_CODEC_DRIFT = {"transport/codec.py": """
    def foo_compress(arr):
        return {"tag": True, "n": 3, "ghost": 1}
    def foo_decompress(d):
        return (d["tag"], d["n"], d["missing"])
"""}


def test_slt010_codec_field_drift_both_directions(tmp_path):
    findings = _lint_tree(tmp_path, _CODEC_DRIFT)
    assert _rules(findings) == ["SLT010", "SLT010"]
    msgs = " ".join(f.message for f in findings)
    assert "ghost" in msgs and "missing" in msgs


def test_slt010_matched_codec_is_clean(tmp_path):
    findings = _lint_tree(tmp_path, {"transport/codec.py": """
        def foo_compress(arr):
            return {"tag": True, "n": 3}
        def foo_decompress(d):
            return (d["tag"], d["n"])
    """})
    assert findings == []


def test_slt010_http_reply_field_never_read(tmp_path):
    findings = _lint_tree(tmp_path, {"transport/http.py": """
        class HttpTransport:
            def split_step(self, acts, step):
                out = self._post("/forward_pass",
                                 {"acts": acts, "step": step})
                return out["grads"], float(out["loss"])
        def handle_forward(req, runtime):
            grads, loss = runtime.split_step(req["acts"], req["step"])
            resp = {"grads": grads, "loss": loss, "debug": 1}
            return resp
    """})
    assert _rules(findings) == ["SLT010"]
    assert "debug" in findings[0].message


def test_slt010_native_binding_pairing(tmp_path):
    cc = (tmp_path / "native")
    cc.mkdir(parents=True, exist_ok=True)
    (cc / "slt_codec.cc").write_text(
        'extern "C" {\n'
        "int slt_encode(const char* buf) {\n  return 0;\n}\n"
        "int slt_unused(int x) {\n  return 1;\n}\n"
        "}\n")
    findings = _lint_tree(tmp_path, {"native/codec.py": """
        lib = None
        def encode(buf):
            return lib.slt_encode(buf)
        def missing(buf):
            return lib.slt_missing(buf)
    """})
    assert _rules(findings) == ["SLT010", "SLT010"]
    msgs = " ".join(f.message for f in findings)
    assert "slt_missing" in msgs and "slt_unused" in msgs


def test_slt010_waiver_file(tmp_path):
    for rel, srctext in _CODEC_DRIFT.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(srctext))
    wf = tmp_path / "waivers"
    wf.write_text("SLT010 transport/codec.py legacy peer still sends it\n")
    assert engine.main([str(tmp_path), "--waiver-file", str(wf)]) == 0
    assert engine.main([str(tmp_path)]) == 1


# ---------------------------------------------------------------------- #
# SLT011: condition wait() outside a while-predicate loop
# ---------------------------------------------------------------------- #

def test_slt011_bare_and_if_guarded_wait(tmp_path):
    findings = _lint(tmp_path, "runtime/coalesce.py", """
        class Coalescer:
            def a(self):
                with self._cond:
                    self._cond.wait(timeout=1.0)      # bare: flagged
            def b(self):
                with self.cv:
                    if not self.ready:
                        self.cv.wait()                # if-guard: flagged
    """)
    assert _rules(findings) == ["SLT011", "SLT011"]
    msgs = " ".join(f.message for f in findings)
    assert "while" in msgs


def test_slt011_while_wrapped_and_wait_for_are_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/coalesce.py", """
        class Coalescer:
            def a(self):
                with self._cond:
                    while not self.ready:
                        self._cond.wait(timeout=1.0)
            def b(self):
                with self.cv:
                    self.cv.wait_for(lambda: self.ready, timeout=1.0)
            def c(self):
                while True:
                    with self._cond:
                        self._cond.wait()   # enclosing while counts
    """)
    assert findings == []


def test_slt011_nested_def_resets_loop_scope(tmp_path):
    # the while loop belongs to the outer function; a wait() inside a
    # nested def is NOT protected by it
    findings = _lint(tmp_path, "runtime/fleet.py", """
        class Fleet:
            def run(self):
                while self.alive:
                    def poke():
                        with self._cond:
                            self._cond.wait()
                    poke()
    """)
    assert _rules(findings) == ["SLT011"]


def test_slt011_scoped_to_runtime_and_transport(tmp_path):
    findings = _lint(tmp_path, "examples/demo.py", """
        class Demo:
            def f(self):
                with self._cond:
                    self._cond.wait()
    """)
    assert findings == []


def test_slt011_waiver_file(tmp_path):
    bad = tmp_path / "runtime" / "coalesce.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        class C:
            def f(self):
                with self._cond:
                    self._cond.wait()
    """))
    wf = tmp_path / "waivers"
    wf.write_text("SLT011 runtime/coalesce.py single-waiter, "
                  "timeout-bounded\n")
    assert engine.main([str(tmp_path), "--waiver-file", str(wf)]) == 0
    assert engine.main([str(tmp_path)]) == 1


# ---------------------------------------------------------------------- #
# SLT012: state.params reads on a deferred-apply runtime need the lock
# ---------------------------------------------------------------------- #

def test_slt012_unlocked_params_read_flagged(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        class ServerRuntime:
            def __init__(self):
                self._deferred = object()
            def peek(self):
                return self.state.params          # unlocked: flagged
            def hook(self):
                def cb():
                    return self.state.params      # nested def: flagged
                return cb
    """)
    assert _rules(findings) == ["SLT012", "SLT012"]
    msgs = " ".join(f.message for f in findings)
    assert "apply lock" in msgs and "export_state" in msgs


def test_slt012_locked_and_barrier_reads_are_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        class ServerRuntime:
            def __init__(self):
                self._deferred = object()
            def locked(self):
                with self._lock:
                    return self.state.params
            def export_state(self):
                self._deferred.flush()
                return self.state.params          # the flush barrier
            def flush_deferred(self):
                return self.state.params
    """)
    assert findings == []


def test_slt012_scoped_to_deferred_owning_classes(tmp_path):
    # a runtime class WITHOUT a deferred queue has no stale-params
    # hazard — its unlocked reads stay legal (the client trainer shape)
    findings = _lint(tmp_path, "runtime/client.py", """
        class SplitClientTrainer:
            def loss_params(self):
                return self.state.params
    """)
    assert findings == []
    # ...and files outside runtime/ are out of scope entirely
    findings = _lint(tmp_path, "launch/run.py", """
        class Driver:
            def __init__(self):
                self._deferred = object()
            def peek(self):
                return self.state.params
    """)
    assert findings == []


def test_slt012_waiver_file(tmp_path):
    bad = tmp_path / "runtime" / "server.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        class ServerRuntime:
            def __init__(self):
                self._deferred = object()
            def peek(self):
                return self.state.params
    """))
    wf = tmp_path / "waivers"
    wf.write_text("SLT012 runtime/server.py read-only debug probe\n")
    assert engine.main([str(tmp_path), "--waiver-file", str(wf)]) == 0
    assert engine.main([str(tmp_path)]) == 1


# ---------------------------------------------------------------------- #
# SLT013: sharded outputs cross D2H via the sanctioned per-shard gather
# ---------------------------------------------------------------------- #

def test_slt013_raw_gather_in_expected_d2h_flagged(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        import numpy as np
        import jax
        class ServerRuntime:
            def __init__(self):
                self._mesh = object()
            def step(self, tag):
                with obs_dispatch.expected_d2h(tag):
                    g = np.asarray(self.g_dev)       # raw shard gather
                    e = np.array(self.e_dev)         # same, via np.array
                    h = jax.device_get(self.h_dev)   # same, via jax
                return g, e, h
    """)
    assert _rules(findings) == ["SLT013", "SLT013", "SLT013"]
    msgs = " ".join(f.message for f in findings)
    assert "_host_gather" in msgs and "per-shard" in msgs


def test_slt013_sanctioned_gather_and_off_path_reads_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        import numpy as np
        class ServerRuntime:
            def __init__(self):
                self._mesh = object()
            def step(self, tag):
                with obs_dispatch.expected_d2h(tag):
                    g = self._host_gather(self.g_dev)   # the seam
                    cb = lambda: np.asarray(self.x)     # runs later
                n = np.asarray(self.host_buf)           # outside the block
                return g, cb, n
    """)
    assert findings == []


def test_slt013_scoped_to_mesh_aware_runtime_classes(tmp_path):
    # a runtime class with NO mesh attributes has single-device outputs
    # — np.asarray on them is the normal (and correct) materialization
    findings = _lint(tmp_path, "runtime/client.py", """
        import numpy as np
        class SplitClientTrainer:
            def step(self, tag):
                with obs_dispatch.expected_d2h(tag):
                    return np.asarray(self.g_dev)
    """)
    assert findings == []
    # ...and files outside runtime/ are out of scope entirely
    findings = _lint(tmp_path, "launch/run.py", """
        import numpy as np
        class Driver:
            def __init__(self):
                self._mesh = object()
            def step(self, tag):
                with obs_dispatch.expected_d2h(tag):
                    return np.asarray(self.g_dev)
    """)
    assert findings == []


def test_slt013_waiver_file(tmp_path):
    bad = tmp_path / "runtime" / "server.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        import numpy as np
        class ServerRuntime:
            def __init__(self):
                self._mesh = object()
            def step(self, tag):
                with obs_dispatch.expected_d2h(tag):
                    return np.asarray(self.g_dev)
    """))
    wf = tmp_path / "waivers"
    wf.write_text("SLT013 runtime/server.py replicated-only debug path\n")
    assert engine.main([str(tmp_path), "--waiver-file", str(wf)]) == 0
    assert engine.main([str(tmp_path)]) == 1


# ---------------------------------------------------------------------- #
# SLT014: persistence discipline (crash-atomic writes + field pairing)
# ---------------------------------------------------------------------- #

def test_slt014_flags_in_place_writes(tmp_path):
    findings = _lint(tmp_path, "runtime/ckpt.py", """
        import pickle
        def save_meta(path, text):
            with open(path, "w") as f:
                f.write(text)
        def save_blob(path, obj):
            with open(path, "wb") as f:
                pickle.dump(obj, f)
    """)
    # the bare open(...,'w'), the open(...,'wb'), and pickle.dump
    assert _rules(findings) == ["SLT014", "SLT014", "SLT014"]
    msgs = " ".join(f.message for f in findings)
    assert "rename" in msgs or "atomic" in msgs


def test_slt014_tmp_write_rename_idiom_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/ckpt.py", """
        import os
        def save_meta(path, text):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(text)
            os.replace(tmp, path)
        class _OsFS:
            def put(self, path, text):
                with open(path, "w") as f:
                    f.write(text)
            def rename(self, src, dst):
                os.replace(src, dst)
        def read(path):
            with open(path) as f:
                return f.read()
    """)
    assert findings == []
    # files outside runtime/ are out of scope for part A
    findings = _lint(tmp_path, "scripts/dump.py", """
        def save(path, text):
            with open(path, "w") as f:
                f.write(text)
    """)
    assert findings == []


def test_slt014_inline_waiver(tmp_path):
    findings = _lint(tmp_path, "runtime/ckpt.py", """
        def save(path, text):
            with open(path, "w") as f:  # slt-lint: disable=SLT014 (scratch file, rebuilt on boot)
                f.write(text)
    """)
    assert _rules(findings, waived=True) == ["SLT014"]
    assert _rules(findings, waived=False) == []


def test_slt014_waiver_file(tmp_path):
    bad = tmp_path / "runtime" / "ckpt.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        def save(path, text):
            with open(path, "w") as f:
                f.write(text)
    """))
    wf = tmp_path / "waivers"
    wf.write_text("SLT014 runtime/ckpt.py legacy dump path, migration tracked\n")
    assert engine.main([str(tmp_path), "--waiver-file", str(wf)]) == 0
    assert engine.main([str(tmp_path)]) == 1


def test_slt014_pairing_cross_file(tmp_path):
    # exporter writes "ghost" nobody restores; restorer hard-reads
    # "missing" nobody exports — both cross-file findings
    exp = tmp_path / "runtime" / "state.py"
    exp.parent.mkdir(parents=True)
    exp.write_text(textwrap.dedent("""
        def export_state(self):
            return {"step": 1, "ghost": 2}
    """))
    res = tmp_path / "transport" / "wire.py"
    res.parent.mkdir(parents=True)
    res.write_text(textwrap.dedent("""
        def restore_state(self, rec):
            step = rec["step"]
            val = rec["missing"]
            return step, val
    """))
    findings = [f for f in engine.lint_paths([str(tmp_path)])
                if f.rule == "SLT014"]
    msgs = " ".join(f.message for f in findings)
    assert "ghost" in msgs
    assert "missing" in msgs


def test_slt014_pairing_matched_fields_clean(tmp_path):
    exp = tmp_path / "runtime" / "state.py"
    exp.parent.mkdir(parents=True)
    exp.write_text(textwrap.dedent("""
        def export_state(self):
            return {"step": 1, "replay": []}
    """))
    res = tmp_path / "runtime" / "boot.py"
    res.write_text(textwrap.dedent("""
        def restore_state(self, rec):
            return rec["step"], rec.get("replay", [])
    """))
    findings = [f for f in engine.lint_paths([str(tmp_path)])
                if f.rule == "SLT014"]
    assert findings == []


# ---------------------------------------------------------------------- #
# SLT015: flight event names come from the spans.py FL_* registry
# ---------------------------------------------------------------------- #

def test_slt015_flags_literal_and_unregistered(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        from split_learning_tpu.obs import flight as obs_flight
        from split_learning_tpu.obs import spans
        def step(self):
            fl = obs_flight.get_recorder()
            if fl is not None:
                fl.record("my_event", step=1)
                fl.record(spans.FL_BOGUS, step=1)
    """)
    rules = _rules(findings)
    # the literal also co-fires SLT003 (same sink, same registry
    # discipline) — SLT015 must flag both the literal and the
    # unregistered constant
    assert rules.count("SLT015") == 2
    msgs = " ".join(f.message for f in findings if f.rule == "SLT015")
    assert "my_event" in msgs and "FL_BOGUS" in msgs


def test_slt015_registered_constant_and_scope_clean(tmp_path):
    findings = _lint(tmp_path, "runtime/server.py", """
        from split_learning_tpu.obs import spans
        def step(self, fl):
            if fl is not None:
                fl.record(spans.FL_DISPATCH, step=3, client_id=0)
        def trace(self, tr):
            tr.record(spans.DISPATCH, 0.0, 0.1)
    """)
    assert [f for f in findings if f.rule == "SLT015"] == []
    # non-flight receivers and out-of-scope dirs never fire
    findings = _lint(tmp_path, "models/demo.py", """
        def f(fl):
            fl.record("free_text")
    """)
    assert [f for f in findings if f.rule == "SLT015"] == []


def test_slt015_inline_waiver(tmp_path):
    findings = _lint(tmp_path, "transport/wire.py", """
        def f(fl):
            fl.record(FL_EXPERIMENTAL)  # slt-lint: disable=SLT015 (prototype event, registered next PR)
    """)
    assert _rules(findings, waived=True) == ["SLT015"]
    assert _rules(findings, waived=False) == []


# ---------------------------------------------------------------------- #
# engine: exit codes, waiver file, real tree
# ---------------------------------------------------------------------- #

def test_main_exit_codes(tmp_path, capsys):
    bad = tmp_path / "runtime" / "server.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        import numpy as np
        class ServerRuntime:
            def f(self):
                with self._lock:
                    return np.asarray(self.dev)
    """))
    assert engine.main([str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "SLT001" in out and "server.py:6" in out  # file:line carried
    bad.write_text("x = 1\n")
    assert engine.main([str(tmp_path)]) == 0


def test_waiver_file_scoped_waiver(tmp_path):
    bad = tmp_path / "runtime" / "server.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        import numpy as np
        class ServerRuntime:
            def f(self):
                with self._lock:
                    return np.asarray(self.dev)
    """))
    wf = tmp_path / "waivers"
    wf.write_text("SLT001 runtime/server.py quarantined pending refactor\n")
    assert engine.main([str(tmp_path), "--waiver-file", str(wf)]) == 0
    wf.write_text("SLT001\n")  # malformed: no path/reason
    assert engine.main([str(tmp_path), "--waiver-file", str(wf)]) == 1


def test_syntax_error_is_a_finding(tmp_path):
    p = tmp_path / "runtime" / "broken.py"
    p.parent.mkdir(parents=True)
    p.write_text("def f(:\n")
    findings = engine.lint_file(str(p))
    assert _rules(findings) == ["SLT000"]


def test_list_rules(capsys):
    assert engine.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("SLT001", "SLT002", "SLT003", "SLT004", "SLT005",
                 "SLT006", "SLT007", "SLT008", "SLT009", "SLT010",
                 "SLT011", "SLT012", "SLT013", "SLT014", "SLT015",
                 # slt-check dynamic-invariant pseudo-rules
                 "SLT100", "SLT101", "SLT102", "SLT103", "SLT104",
                 "SLT105", "SLT106", "SLT107", "SLT108",
                 # slt-crash durability invariants
                 "SLT109", "SLT110", "SLT111", "SLT112"):
        assert rule in out


def test_real_tree_has_zero_unwaived_findings():
    """The acceptance gate: the shipped tree lints clean."""
    findings = engine.lint_paths([str(REPO / "split_learning_tpu"),
                                  str(REPO / "scripts")],
                                 waiver_file=str(REPO / ".slt-lint.waivers"))
    unwaived = [f for f in findings if not f.waived]
    assert unwaived == [], "\n".join(f.format() for f in unwaived)


# ---------------------------------------------------------------------- #
# spans registry: drift guards
# ---------------------------------------------------------------------- #

def test_trace_reexports_spans_tuples():
    assert obs_trace.CLIENT_PHASES == spans.CLIENT_PHASES
    assert obs_trace.SERVER_PHASES == spans.SERVER_PHASES


def test_trace_report_fallback_matches_registry():
    """scripts/trace_report.py runs standalone, so it keeps a literal
    fallback copy of the phase tuples — pinned here to the registry."""
    tree = ast.parse((REPO / "scripts" / "trace_report.py").read_text())
    fallback = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        for h in node.handlers:
            if getattr(h.type, "id", None) != "ImportError":
                continue
            for s in h.body:
                if (isinstance(s, ast.Assign)
                        and isinstance(s.targets[0], ast.Name)):
                    fallback[s.targets[0].id] = ast.literal_eval(s.value)
    assert fallback["CLIENT_PHASES"] == spans.CLIENT_PHASES
    assert fallback["TRANSPORT_SUB"] == spans.TRANSPORT_SUB
    assert fallback["COMPILE"] == spans.COMPILE
    assert fallback["REPLY_GRAD"] == spans.REPLY_GRAD
    assert fallback["DEFERRED_APPLY"] == spans.DEFERRED_APPLY
    assert fallback["MESH_META"] == spans.MESH_META
    assert fallback["STAGE_META"] == spans.STAGE_META


def test_postmortem_fallback_matches_registry():
    """scripts/postmortem.py runs standalone too: its ImportError
    fallback of FL_* event names is pinned byte-equal to the
    obs/spans.py registry."""
    tree = ast.parse((REPO / "scripts" / "postmortem.py").read_text())
    fallback = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        for h in node.handlers:
            if getattr(h.type, "id", None) != "ImportError":
                continue
            for s in h.body:
                if (isinstance(s, ast.Assign)
                        and isinstance(s.targets[0], ast.Name)):
                    fallback[s.targets[0].id] = ast.literal_eval(s.value)
    assert fallback, "postmortem.py lost its ImportError fallback"
    registered = {k for k in vars(spans) if k.startswith("FL_")}
    assert set(fallback) <= registered
    for name, value in fallback.items():
        assert getattr(spans, name) == value, name


def test_analysis_package_is_stdlib_only():
    """The CI lint step must not need jax/numpy: the analysis package
    imports nothing outside the stdlib and itself."""
    import importlib
    # sched/invariants are pinned too: the model checker itself must
    # run on the lint image (scenarios.py is the one module allowed to
    # import numpy/the runtime, and the engine only loads it lazily
    # under --check)
    for mod in ("engine", "rules", "rules_jax", "cfg", "sched",
                "invariants"):
        m = importlib.import_module(f"split_learning_tpu.analysis.{mod}")
        src = Path(m.__file__).read_text()
        tree = ast.parse(src)
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                names = [node.module]
            for name in names:
                root = name.split(".")[0]
                assert root not in ("jax", "numpy", "requests"), (
                    f"{mod}.py imports {name}")


# ---------------------------------------------------------------------- #
# obs/locks.py: the dynamic watchdog
# ---------------------------------------------------------------------- #

def test_intentional_inversion_is_detected():
    g = locks.LockGraph()
    a = locks.InstrumentedLock("A", graph=g, budget_s=None)
    b = locks.InstrumentedLock("B", graph=g, budget_s=None)
    with a:
        with b:
            pass
    assert g.violations == []  # one order alone is fine
    with b:
        with a:
            pass
    kinds = [v["kind"] for v in g.violations]
    assert kinds == ["lock-order-inversion"]
    msg = g.violations[0]["message"]
    assert "A" in msg and "B" in msg
    # repeated inversions of the same pair are reported once
    with b:
        with a:
            pass
    assert len(g.violations) == 1


def test_hold_budget_violation():
    g = locks.LockGraph()
    h = locks.InstrumentedLock("H", graph=g, budget_s=0.001)
    with h:
        time.sleep(0.01)
    assert [v["kind"] for v in g.violations] == ["hold-budget"]
    ok = locks.InstrumentedLock("OK", graph=g, budget_s=10.0)
    with ok:
        pass
    assert len(g.violations) == 1


def test_reentrant_acquire_is_not_an_edge_and_hold_spans_outermost():
    g = locks.LockGraph()
    reg = Registry()
    l = locks.InstrumentedLock("R", graph=g, registry=reg, budget_s=None)
    with l:
        with l:  # reentrant
            pass
    assert g.violations == [] and g.edges == {}
    snap = reg.snapshot()["histograms"]
    assert snap[spans.LOCK_HOLD]["count"] == 1  # one outermost hold


def test_condition_interop():
    g = locks.LockGraph()
    cv = threading.Condition(locks.InstrumentedLock("CV", graph=g,
                                                    budget_s=None))
    hits = []

    def waiter():
        with cv:
            while not hits:
                cv.wait(timeout=5.0)
            hits.append("woke")

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cv:
        hits.append("notified")
        cv.notify()
    t.join(timeout=5.0)
    assert not t.is_alive() and hits == ["notified", "woke"]
    assert g.violations == []


def test_make_lock_off_returns_plain_threading_primitives(monkeypatch):
    monkeypatch.delenv("SLT_LOCK_DEBUG", raising=False)
    assert isinstance(locks.make_lock("x"), type(threading.RLock()))
    assert isinstance(locks.make_lock("x", reentrant=False),
                      type(threading.Lock()))


def test_make_lock_on_instruments_runtime_components(monkeypatch):
    monkeypatch.setenv("SLT_LOCK_DEBUG", "1")
    from split_learning_tpu.runtime.coalesce import RequestCoalescer
    from split_learning_tpu.runtime.replay import ReplayCache
    assert isinstance(locks.make_lock("x"), locks.InstrumentedLock)
    cache = ReplayCache()
    assert isinstance(cache._lock, locks.InstrumentedLock)
    co = RequestCoalescer(lambda group, reason: None, max_group=2,
                          window_s=0.0)
    try:
        assert isinstance(co._cond._lock, locks.InstrumentedLock)
    finally:
        co.close()


def test_watchdog_loss_series_bit_identical(monkeypatch):
    """SLT_LOCK_DEBUG instruments the locks and nothing else: the same
    three steps produce a bit-identical loss series on and off — and
    the off path (the shipped default) uses plain threading locks, so
    the wire cannot change."""
    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    def series(debug):
        if debug:
            monkeypatch.setenv("SLT_LOCK_DEBUG", "1")
        else:
            monkeypatch.delenv("SLT_LOCK_DEBUG", raising=False)
        cfg = Config(mode="split", batch_size=4, num_clients=1)
        plan = get_plan(mode="split")
        sample = np.zeros((4, 28, 28, 1), np.float32)
        server = ServerRuntime(plan, cfg, jax.random.PRNGKey(2), sample)
        if debug:
            assert isinstance(server._lock, locks.InstrumentedLock)
        else:
            assert isinstance(server._lock, type(threading.RLock()))
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    LocalTransport(server))
        rs = np.random.RandomState(7)
        try:
            return [client.train_step(
                rs.randn(4, 28, 28, 1).astype(np.float32),
                rs.randint(0, 10, 4).astype(np.int64), i)
                for i in range(3)]
        finally:
            server.close()

    on = series(True)
    assert locks.default_graph().violations == []
    assert on == series(False)


# ---------------------------------------------------------------------- #
# obs/dispatch_debug.py: the dispatch watchdog
# ---------------------------------------------------------------------- #

def _with_listener(t):
    """Feed jax.monitoring compile events into a private tracker; the
    returned callable detaches it (best-effort: the unregister hook is
    a private API)."""
    def listener(event, secs, **_kw):
        t.on_compile_event(event, secs)
    jax.monitoring.register_event_duration_secs_listener(listener)

    def detach():
        try:
            from jax._src import monitoring as _mon
            _mon._unregister_event_duration_listener_by_callback(listener)
        except Exception:
            pass
    return detach


def test_dispatch_tracker_flags_steady_state_recompile():
    """A jit whose static arg varies per step compiles on EVERY call;
    from local ordinal 2 on, with the signature already seen, each one
    is a steady-state-recompile violation (deduped per ordinal)."""
    t = dispatch_debug.DispatchTracker()
    detach = _with_listener(t)
    try:
        f = jax.jit(lambda x, n: x * n, static_argnums=(1,))
        x = jnp.ones((4,), jnp.float32)
        for i in range(5):
            with t.scope(("trainer", "step"), sig=(x.shape, "float32")):
                f(x, i).block_until_ready()
    finally:
        detach()
    assert t.compile_count >= 5  # one real backend compile per call
    kinds = [v["kind"] for v in t.violations]
    assert kinds == ["steady-state-recompile"] * 3  # ordinals 2, 3, 4
    assert t.gauges()["steady_state_recompiles"] == 3.0
    assert t.gauges()["compile_count"] == float(t.compile_count)


def test_dispatch_tracker_fresh_signature_is_exempt():
    """New input shapes legitimately compile at any ordinal — the
    signature set marks those scopes fresh and nothing is flagged."""
    t = dispatch_debug.DispatchTracker()
    detach = _with_listener(t)
    try:
        g = jax.jit(lambda x: x * 2.0)
        for n in (3, 4, 5, 6):
            with t.scope("g", sig=((n,), "float32")):
                g(jnp.ones((n,), jnp.float32)).block_until_ready()
    finally:
        detach()
    assert t.compile_count >= 4
    assert t.violations == []


def test_dispatch_guard_error_is_counted_and_reraised():
    """The transfer guard is inert on the CPU backend (module
    docstring), so the reporting path is exercised with a synthetic
    guard-shaped error: counted, reported, re-raised."""
    t = dispatch_debug.DispatchTracker()
    with pytest.raises(RuntimeError):
        with t.scope("k"):
            raise RuntimeError(
                "Disallowed device-to-host transfer: from platform cpu")
    assert t.unexpected_d2h == 1
    assert [v["kind"] for v in t.violations] == ["unexpected-d2h"]
    assert t.gauges()["unexpected_d2h_total"] == 1.0
    with pytest.raises(RuntimeError):  # unrelated errors pass uncounted
        with t.scope("k"):
            raise RuntimeError("boom")
    assert t.unexpected_d2h == 1


def test_dispatch_helpers_off_are_shared_nullcontext(monkeypatch):
    monkeypatch.delenv("SLT_DISPATCH_DEBUG", raising=False)
    assert dispatch_debug.attach() is None
    assert (dispatch_debug.step_scope(None, "k")
            is dispatch_debug.expected_d2h(None))


def test_dispatch_force_enables_attach(monkeypatch):
    externally_on = dispatch_debug.enabled()
    monkeypatch.delenv("SLT_DISPATCH_DEBUG", raising=False)
    dispatch_debug.force(True)
    try:
        t = dispatch_debug.attach()
        assert t is dispatch_debug.tracker()
        assert set(t.gauges()) == {"compile_count",
                                   "unexpected_d2h_total",
                                   "steady_state_recompiles"}
    finally:
        dispatch_debug.force(False)
        if not externally_on:
            dispatch_debug.uninstall()


def test_dispatch_watchdog_loss_series_bit_identical(monkeypatch):
    """SLT_DISPATCH_DEBUG wraps the jitted calls in scopes and nothing
    else: the same three steps produce a bit-identical loss series on
    and off — and on the shipped default (off) every hook is None and
    step_scope/expected_d2h return the shared nullcontext."""
    from split_learning_tpu.models import get_plan
    from split_learning_tpu.runtime import ServerRuntime, SplitClientTrainer
    from split_learning_tpu.transport.local import LocalTransport
    from split_learning_tpu.utils import Config

    externally_on = dispatch_debug.enabled()

    def series(debug):
        if debug:
            monkeypatch.setenv("SLT_DISPATCH_DEBUG", "1")
        else:
            monkeypatch.delenv("SLT_DISPATCH_DEBUG", raising=False)
        cfg = Config(mode="split", batch_size=4, num_clients=1)
        plan = get_plan(mode="split")
        sample = np.zeros((4, 28, 28, 1), np.float32)
        server = ServerRuntime(plan, cfg, jax.random.PRNGKey(2), sample)
        assert (server._dd is not None) is debug
        client = SplitClientTrainer(plan, cfg, jax.random.PRNGKey(0),
                                    LocalTransport(server))
        assert (client._dd is not None) is debug
        rs = np.random.RandomState(7)
        try:
            return [client.train_step(
                rs.randn(4, 28, 28, 1).astype(np.float32),
                rs.randint(0, 10, 4).astype(np.int64), i)
                for i in range(3)]
        finally:
            server.close()

    try:
        on = series(True)
        # steady steps over fixed shapes: no watchdog report
        assert dispatch_debug.tracker().violations == []
        assert on == series(False)
    finally:
        if not externally_on:
            dispatch_debug.uninstall()
