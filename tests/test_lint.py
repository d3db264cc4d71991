"""pstpu-lint rule suite: every rule code with a firing and a non-firing
fixture, the waiver machinery, and the live-repo-lints-clean gate.

The fixtures build miniature project trees (the per-file rules scope by
project-relative path, so files land under production_stack_tpu/...) and
run through the real driver; the project-level rules (PL004/PL006) are
exercised through their check functions with synthetic sources. The final
test lints the actual repository — a regression that introduces a finding
fails tier-1 here, not just the CI lint job.
"""

import os
import sys
import textwrap

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.pstpu_lint import run_lint  # noqa: E402
from tools.pstpu_lint.core import Finding, main, parse_waivers  # noqa: E402


def _write(tmp_path, relpath, source):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return path


def _lint(tmp_path, *relpaths):
    return run_lint(
        [str(tmp_path / r) for r in relpaths],
        project_root=str(tmp_path), project_rules=False,
    )


def _codes(findings):
    return [f.rule for f in findings]


ROUTER_FILE = "production_stack_tpu/router/mod.py"


# ---------------------------------------------------------------------- PL001
class TestBlockedEventLoop:
    def test_fires_on_sleep_in_async_def(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import time

            async def handler(request):
                time.sleep(0.5)
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL001"]
        assert "time.sleep" in findings[0].message

    def test_fires_through_sync_helper_call_chain(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import requests

            async def handler(request):
                return _fetch()

            def _fetch():
                return requests.get("http://backend/metrics")
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL001"]
        assert "reachable from async def handler" in findings[0].message

    def test_thread_target_is_exempt(self, tmp_path):
        # The stats-scraper shape: a daemon-thread worker loop may sleep
        # and use requests; nothing async calls it, so no finding.
        _write(tmp_path, ROUTER_FILE, """
            import threading
            import time
            import requests

            class Scraper:
                def start(self):
                    self._thread = threading.Thread(
                        target=self._worker, daemon=True
                    )
                    self._thread.start()

                def _worker(self):
                    while True:
                        requests.get("http://engine/metrics")
                        time.sleep(10)
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_executor_target_is_exempt(self, tmp_path):
        # The files-service shape: blocking I/O in a nested def handed to
        # run_in_executor runs off-loop — a reference, not a call.
        _write(tmp_path, ROUTER_FILE, """
            import asyncio

            async def save(content):
                def _write():
                    with open("/tmp/x", "wb") as f:
                        f.write(content)

                loop = asyncio.get_running_loop()
                await loop.run_in_executor(None, _write)
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_out_of_scope_package_not_checked(self, tmp_path):
        # PL001 scopes to the data-plane packages; the engine tier runs
        # its blocking work on executors by design.
        rel = "production_stack_tpu/engine/mod.py"
        _write(tmp_path, rel, """
            import time

            async def loop_step():
                time.sleep(1)
        """)
        assert "PL001" not in _codes(_lint(tmp_path, rel))


# ---------------------------------------------------------------------- PL002
class TestFireAndForget:
    def test_fires_on_dropped_create_task(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import asyncio

            async def go(coro):
                asyncio.create_task(coro)
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL002"]

    def test_fires_on_underscore_ensure_future(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import asyncio

            async def go(coro):
                _ = asyncio.ensure_future(coro)
        """)
        assert _codes(_lint(tmp_path, ROUTER_FILE)) == ["PL002"]

    def test_non_asyncio_receivers_are_clean(self, tmp_path):
        # A domain method named create_task is not an asyncio spawn, and
        # TaskGroup.create_task holds a strong ref + propagates exceptions.
        _write(tmp_path, ROUTER_FILE, """
            import asyncio

            async def a(self):
                self.scheduler.create_task("prefill")

            async def b():
                async with asyncio.TaskGroup() as tg:
                    tg.create_task(work())
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_loop_receiver_fires(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import asyncio

            async def go(coro):
                asyncio.get_event_loop().create_task(coro)
        """)
        assert _codes(_lint(tmp_path, ROUTER_FILE)) == ["PL002"]

    def test_stored_handle_is_clean(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import asyncio

            class Engine:
                async def start(self, coro, other):
                    self._task = asyncio.create_task(coro)
                    t = asyncio.ensure_future(other)
                    self._tasks.add(t)
                    t.add_done_callback(self._tasks.discard)
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []


# ---------------------------------------------------------------------- PL003
class TestSwallowedExceptions:
    def test_fires_on_silent_catch_all(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            def probe(url):
                try:
                    return fetch(url)
                except Exception:
                    return []
        """)
        assert _codes(_lint(tmp_path, ROUTER_FILE)) == ["PL003"]

    def test_fires_on_bare_except_pass(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            def close(sock):
                try:
                    sock.close()
                except:
                    pass
        """)
        assert _codes(_lint(tmp_path, ROUTER_FILE)) == ["PL003"]

    def test_logged_metric_or_used_exception_is_clean(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            def a(logger):
                try:
                    work()
                except Exception:
                    logger.exception("work failed")

            def b(self):
                try:
                    work()
                except Exception:
                    self.failures_total += 1

            def c(metrics):
                try:
                    work()
                except Exception:
                    metrics.errors.labels(kind="x").inc()

            def d():
                try:
                    work()
                except Exception as e:
                    return error_response(400, f"failed: {e}")

            def e_():
                try:
                    work()
                except ValueError:
                    return None   # narrow except: not a catch-all

            def f(metrics, url):
                try:
                    work()
                except Exception:
                    # .set() on a metric receiver (labels chain) counts
                    metrics.circuit_state.labels(server=url).set(1)
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_event_set_is_not_metric_evidence(self, tmp_path):
        # Event.set() is a shutdown signal, not failure evidence — the
        # exception is still swallowed silently.
        _write(tmp_path, ROUTER_FILE, """
            def worker(self):
                try:
                    work()
                except Exception:
                    self._shutdown.set()
        """)
        assert _codes(_lint(tmp_path, ROUTER_FILE)) == ["PL003"]


# ---------------------------------------------------------------------- PL004
class TestMetricsDrift:
    REG = None   # built lazily so import stays at module level

    @staticmethod
    def _registry():
        from tools.pstpu_lint.metrics_registry import ENGINE, ROUTER, Series

        return (
            Series("pstpu:good_total", "counter", ("model_name",),
                   (ENGINE,), ("catalogue",), "doc"),
            Series("router_good_total", "counter", (), (ROUTER,),
                   ("catalogue",), "doc", router_labels=("server",)),
        )

    def _tree(self, tmp_path, server_body=None):
        _write(tmp_path, "production_stack_tpu/server/metrics.py",
               server_body or '''
            def render(s, label):
                return [
                    "# TYPE pstpu:good_total counter",
                    f"pstpu:good_total{label} {s['good']}",
                ]
        ''')
        _write(tmp_path, "production_stack_tpu/engine/metrics.py", """
            class Histogram:
                pass
        """)
        _write(tmp_path, "production_stack_tpu/router/metrics.py", """
            from prometheus_client import Counter

            good = Counter("router_good", "doc", ["server"])
        """)

    def test_clean_tree_passes(self, tmp_path):
        from tools.pstpu_lint.rules.metrics_drift import check_metrics

        self._tree(tmp_path)
        assert check_metrics(str(tmp_path), registry=self._registry(),
                             docs_check=False) == []

    def test_renderer_label_the_registry_lacks_fires(self, tmp_path):
        # The renderer grows a 'role' label the registry does not have.
        from tools.pstpu_lint.rules.metrics_drift import check_metrics

        self._tree(tmp_path, server_body='''
            def render(s, model_name):
                return [
                    "# TYPE pstpu:good_total counter",
                    f'pstpu:good_total{{model_name="{model_name}",'
                    f'role="{s["role"]}"}} 1',
                ]
        ''')
        findings = check_metrics(str(tmp_path), registry=self._registry(),
                                 docs_check=False)
        assert [f.rule for f in findings] == ["PL004"]
        assert "label set" in findings[0].message

    def test_unregistered_series_fires(self, tmp_path):
        from tools.pstpu_lint.rules.metrics_drift import check_metrics

        self._tree(tmp_path, server_body='''
            def render(s, label):
                return [
                    "# TYPE pstpu:good_total counter",
                    f"pstpu:good_total{label} 1",
                    "# TYPE pstpu:sneaky_total counter",
                    f"pstpu:sneaky_total{label} 1",
                ]
        ''')
        findings = check_metrics(str(tmp_path), registry=self._registry(),
                                 docs_check=False)
        assert any("not in the metrics registry" in f.message
                   for f in findings)

    def test_bad_prefix_and_duplicate_fire(self, tmp_path):
        from tools.pstpu_lint.rules.metrics_drift import check_metrics

        self._tree(tmp_path, server_body='''
            def render(s, label):
                return [
                    "# TYPE pstpu:good_total counter",
                    f"pstpu:good_total{label} 1",
                    "# TYPE pstpu:good_total counter",
                    "# TYPE my_rogue_series gauge",
                ]
        ''')
        findings = check_metrics(str(tmp_path), registry=self._registry(),
                                 docs_check=False)
        msgs = " | ".join(f.message for f in findings)
        assert "more than once" in msgs
        assert "naming convention" in msgs

    def test_registered_series_the_renderer_does_not_emit_fires(
            self, tmp_path):
        from tools.pstpu_lint.rules.metrics_drift import check_metrics

        self._tree(tmp_path, server_body='''
            def render(s, label):
                return []
        ''')
        findings = check_metrics(str(tmp_path), registry=self._registry(),
                                 docs_check=False)
        assert [f.rule for f in findings] == ["PL004"]
        assert "pstpu:good_total" in findings[0].message
        assert "does not emit it" in findings[0].message


# ---------------------------------------------------------------------- PL005
class TestAwaitUnderLock:
    def test_fires_on_await_inside_with_lock(self, tmp_path):
        rel = "production_stack_tpu/engine/mod.py"
        _write(tmp_path, rel, """
            async def apply(self, batch):
                with self._lock:
                    await self.runner.dispatch(batch)
        """)
        findings = _lint(tmp_path, rel)
        assert _codes(findings) == ["PL005"]
        assert "_lock" in findings[0].message

    def test_waiver_at_lock_acquisition_site_suppresses(self, tmp_path):
        # Findings anchor to the `with` line, so the natural waiver
        # placement (at the acquisition the message names) works.
        rel = "production_stack_tpu/engine/mod.py"
        _write(tmp_path, rel, """
            async def apply(self, batch):
                # pstpu-lint: allow[PL005] reason=lock is a fake in tests
                with self._lock:
                    await self.runner.dispatch(batch)
        """)
        assert _lint(tmp_path, rel) == []

    def test_async_with_and_no_await_are_clean(self, tmp_path):
        rel = "production_stack_tpu/engine/mod.py"
        _write(tmp_path, rel, """
            async def a(self, batch):
                async with self._lock:
                    await self.runner.dispatch(batch)

            def b(self):
                with self._lock:
                    return dict(self.stats)

            async def c(self, rows):
                with self._lock:
                    self.rows = rows
                await self.flush()
        """)
        assert _lint(tmp_path, rel) == []


# ---------------------------------------------------------------------- PL006
class TestFlagDrift:
    def _tree(self, tmp_path, readme_flags=("--wired",),
              reference_dest=True):
        _write(tmp_path, "production_stack_tpu/router/parser.py", """
            import argparse

            def parse_args():
                p = argparse.ArgumentParser()
                p.add_argument("--wired", default="x", help="used flag")
                p.add_argument("--orphan", default="y", help="dead flag")
                return p.parse_args()
        """)
        # The engine parser reads its own flag in its own tier (references
        # are scoped per parser — see the collision test below).
        _write(tmp_path, "production_stack_tpu/server/api_server.py", """
            import argparse

            def parse_args():
                p = argparse.ArgumentParser()
                p.add_argument("--model", required=True, help="model")
                return p.parse_args()

            def main(args):
                print(args.model)
        """)
        uses = "args.wired" if reference_dest else "None"
        _write(tmp_path, "production_stack_tpu/router/app.py", f"""
            def main(args):
                print({uses}, args.orphan)
        """)
        rows = "\n".join(f"| `{f}` | x | doc |" for f in readme_flags)
        _write(tmp_path, "README.md", f"""
            # readme

            | Flag | Default | What it does |
            |---|---|---|
            {rows}
        """)

    def test_clean_tree_passes(self, tmp_path):
        from tools.pstpu_lint.rules.flag_drift import check_flags

        self._tree(tmp_path,
                   readme_flags=("--wired", "--orphan", "--model"))
        assert check_flags(str(tmp_path)) == []

    def test_undocumented_flag_fires(self, tmp_path):
        from tools.pstpu_lint.rules.flag_drift import check_flags

        self._tree(tmp_path, readme_flags=("--wired", "--model"))
        findings = check_flags(str(tmp_path))
        assert ["PL006"] == [f.rule for f in findings]
        assert "--orphan" in findings[0].message
        assert "not documented" in findings[0].message

    def test_unreferenced_flag_fires(self, tmp_path):
        from tools.pstpu_lint.rules.flag_drift import check_flags

        self._tree(tmp_path,
                   readme_flags=("--wired", "--orphan", "--model"),
                   reference_dest=False)
        findings = check_flags(str(tmp_path))
        assert [f.rule for f in findings] == ["PL006"]
        assert "args.wired is never read" in findings[0].message

    def test_cross_tier_dest_collision_not_pooled(self, tmp_path):
        # --host exists in BOTH parsers (as in the real tree); only the
        # engine tier reads it — the router's copy must still be flagged,
        # not hide behind the other tier's read.
        from tools.pstpu_lint.rules.flag_drift import check_flags

        self._tree(tmp_path,
                   readme_flags=("--wired", "--orphan", "--model",
                                 "--host"))
        _write(tmp_path, "production_stack_tpu/router/parser.py", """
            import argparse

            def parse_args():
                p = argparse.ArgumentParser()
                p.add_argument("--wired", default="x", help="used flag")
                p.add_argument("--orphan", default="y", help="dead flag")
                p.add_argument("--host", default="0.0.0.0", help="bind")
                return p.parse_args()
        """)
        _write(tmp_path, "production_stack_tpu/server/api_server.py", """
            import argparse

            def parse_args():
                p = argparse.ArgumentParser()
                p.add_argument("--model", required=True, help="model")
                p.add_argument("--host", default="0.0.0.0", help="bind")
                return p.parse_args()

            def main(args):
                print(args.model, args.host)
        """)
        findings = check_flags(str(tmp_path))
        assert ["PL006"] == [f.rule for f in findings]
        assert "--host" in findings[0].message
        assert findings[0].file.endswith("router/parser.py")


ENGINE_FILE = "production_stack_tpu/engine/mod.py"


# ---------------------------------------------------------------------- PL007
class TestUseAfterDonate:
    RUNNER = """
        import jax

        class Runner:
            def __init__(self):
                self._decode = jax.jit(self._decode_impl,
                                       donate_argnums=(1, 2))

            def _decode_impl(self, params, kv_k, kv_v):
                return kv_k + 1, kv_v + 1
    """

    def test_read_after_donate_fires(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, self.RUNNER + """
            def bad(self, params):
                toks, other = self._decode(params, self.kv_k, self.kv_v)
                return self.kv_k.sum()
        """)
        findings = _lint(tmp_path, ENGINE_FILE)
        assert _codes(findings) == ["PL007"]
        assert "self.kv_k" in findings[0].message
        assert "donated" in findings[0].message

    def test_same_statement_rebind_is_clean(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, self.RUNNER + """
            def good(self, params):
                self.kv_k, self.kv_v = self._decode(
                    params, self.kv_k, self.kv_v)
                return self.kv_k.sum()
        """)
        assert _lint(tmp_path, ENGINE_FILE) == []

    def test_later_rebind_clears_and_local_donation_tracked(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, self.RUNNER + """
            def later(self, params, wk):
                out = self._decode(params, self.kv_k, wk)
                self.kv_k = out[0]
                return self.kv_k.sum()

            def local_read(self, params, wk):
                out = self._decode(params, self.kv_k, wk)
                self.kv_k = out[0]
                return wk.sum()
        """)
        findings = _lint(tmp_path, ENGINE_FILE)
        assert _codes(findings) == ["PL007"]
        assert "wk" in findings[0].message
        assert "local_read" not in findings[0].message  # anchors at the read
        assert findings[0].render("github").startswith("::error file=")

    def test_retry_guard_exempts_but_bare_except_does_not(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, self.RUNNER + """
            def guarded(self, params):
                out = self._decode(params, self.kv_k, self.kv_v)
                try:
                    return self.kv_k.sum()
                except (RuntimeError, ValueError):
                    return None
        """)
        assert _lint(tmp_path, ENGINE_FILE) == []
        _write(tmp_path, ENGINE_FILE, self.RUNNER + """
            def bare(self, params):
                out = self._decode(params, self.kv_k, self.kv_v)
                try:
                    return self.kv_k.sum()
                except Exception:
                    raise
        """)
        assert _codes(_lint(tmp_path, ENGINE_FILE)) == ["PL007"]

    def test_donate_argnames_spelling_also_fires(self, tmp_path):
        # donate_argnames (names, not positions) resolves against the
        # traced function's parameter list — the analyzer must not go
        # silently blind on the keyword spelling.
        _write(tmp_path, ENGINE_FILE, """
            import jax

            class Runner:
                def __init__(self):
                    self._decode = jax.jit(self._decode_impl,
                                           donate_argnames=("kv_k", "kv_v"))

                def _decode_impl(self, params, kv_k, kv_v):
                    return kv_k + 1, kv_v + 1

                def bad(self, params):
                    toks, other = self._decode(params, self.kv_k, self.kv_v)
                    return self.kv_k.sum()
        """)
        findings = _lint(tmp_path, ENGINE_FILE)
        assert _codes(findings) == ["PL007"]
        assert "self.kv_k" in findings[0].message

    def test_factory_jit_binding_resolves(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, """
            import jax

            class Runner:
                def __init__(self):
                    self._reset = self._make_reset()

                def _make_reset(self):
                    def reset(pool):
                        return pool * 0
                    return jax.jit(reset, donate_argnums=(0,))

                def clear(self):
                    self._reset(self.pool)
                    return self.pool.sum()
        """)
        findings = _lint(tmp_path, ENGINE_FILE)
        assert _codes(findings) == ["PL007"]
        assert "self.pool" in findings[0].message

    def test_out_of_scope_package_not_checked(self, tmp_path):
        rel = "production_stack_tpu/router/mod.py"
        _write(tmp_path, rel, self.RUNNER + """
            def bad(self, params):
                toks, other = self._decode(params, self.kv_k, self.kv_v)
                return self.kv_k.sum()
        """)
        assert "PL007" not in _codes(_lint(tmp_path, rel))


# ---------------------------------------------------------------------- PL008
class TestTraceHazards:
    def test_item_in_jitted_fn_fires(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, """
            import jax

            @jax.jit
            def step(x):
                return x.item()
        """)
        findings = _lint(tmp_path, ENGINE_FILE)
        assert _codes(findings) == ["PL008"]
        assert ".item()" in findings[0].message

    def test_item_in_scan_body_fires_via_chain(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, """
            import jax

            def run(xs):
                def body(carry, x):
                    carry = carry + _peek(x)
                    return carry, x
                return jax.lax.scan(body, 0, xs)

            def _peek(x):
                return x.item()
        """)
        findings = _lint(tmp_path, ENGINE_FILE)
        assert _codes(findings) == ["PL008"]
        assert "traced via" in findings[0].message

    def test_branch_on_tracer_fires_static_and_meta_clean(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, """
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("flag",))
            def go(x, flag, win=None):
                if flag:                    # static argname: clean
                    x = x + 1
                if x.shape[0] > 1:          # shape metadata: clean
                    x = x + 2
                if win is not None:         # optional-arg dispatch: clean
                    x = x + win
                if x > 0:                   # tracer branch: fires
                    x = x + 3
                return x
        """)
        findings = _lint(tmp_path, ENGINE_FILE)
        assert _codes(findings) == ["PL008"]
        assert "'x'" in findings[0].message

    def test_varying_static_arg_at_call_site_fires(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, """
            import time

            import jax

            class R:
                def __init__(self):
                    self._step = jax.jit(self._impl,
                                         static_argnames=("n",))

                def _impl(self, x, n):
                    return x * n

                def hot(self, x, n):
                    return self._step(x, n=n)          # bucketed: clean

                def churn(self, x):
                    return self._step(x, n=time.time())  # fires
        """)
        findings = _lint(tmp_path, ENGINE_FILE)
        assert _codes(findings) == ["PL008"]
        assert "per-call-varying" in findings[0].message

    def test_host_code_outside_trace_is_clean(self, tmp_path):
        _write(tmp_path, ENGINE_FILE, """
            import numpy as np

            def read_blocks(pool, ids):
                return np.asarray(pool)[ids].item()
        """)
        assert _lint(tmp_path, ENGINE_FILE) == []


# ---------------------------------------------------------------------- PL009
class TestSharedStateRace:
    def test_rmw_across_await_fires(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            class Router:
                async def bump(self):
                    n = self.total
                    await self.flush()
                    self.total = n + 1
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL009"]
        assert "read before the await" in findings[0].message

    def test_rmw_under_async_lock_is_clean(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            class Router:
                async def bump(self):
                    async with self._lock:
                        n = self.total
                        await self.flush()
                        self.total = n + 1

                async def no_await(self):
                    n = self.total
                    self.total = n + 1

                async def unrelated_write(self, fresh):
                    await self.flush()
                    self.stats = fresh
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_loop_body_accumulator_is_clean(self, tmp_path):
        # Read and write are ADJACENT inside the loop body (the await
        # comes after the write): the event loop cannot interleave between
        # them, so no lost update — while the classic RMW-across-await
        # inside a loop still fires.
        _write(tmp_path, ROUTER_FILE, """
            class Relay:
                async def pump(self, stream):
                    async for chunk in stream:
                        self.bytes_sent = self.bytes_sent + len(chunk)
                        await self.send(chunk)
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []
        _write(tmp_path, ROUTER_FILE, """
            class Relay:
                async def pump(self, stream):
                    async for chunk in stream:
                        n = self.bytes_sent
                        await self.send(chunk)
                        self.bytes_sent = n + len(chunk)
        """)
        assert _codes(_lint(tmp_path, ROUTER_FILE)) == ["PL009"]

    def test_deferred_lambda_read_is_not_taint(self, tmp_path):
        # A lambda reading self.x evaluates at CALL time, not where it is
        # written — it must not taint the local as derived-from-self.x.
        _write(tmp_path, ROUTER_FILE, """
            class Relay:
                async def go(self):
                    cb = lambda: self.x
                    await self.flush()
                    self.x = self.compute(cb)
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_cross_context_unlocked_mutation_fires(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import threading

            class Stats:
                def start(self):
                    self._thread = threading.Thread(
                        target=self._worker, daemon=True)
                    self._thread.start()

                def _worker(self):
                    self.passes += 1

                def reset(self):
                    with self._lock:
                        self.passes = 0
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL009"]
        assert "self.passes" in findings[0].message
        assert "without the lock" in findings[0].message

    def test_atomic_swap_and_helper_under_lock_are_clean(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import threading

            class Stats:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stats = {}
                    self._load()          # ctor-only helper: clean

                def _load(self):
                    self.stats = {"boot": 1}

                def start(self):
                    self._thread = threading.Thread(
                        target=self._worker, daemon=True)
                    self._thread.start()

                def _worker(self):
                    fresh = {"x": 1}
                    with self._lock:
                        self.stats = fresh

                def _store(self):
                    self.stats = {}       # only ever called under the lock

                def reset(self):
                    with self._lock:
                        self._store()
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []


# ---------------------------------------------------------------------- PL010
class TestWireDrift:
    def _registry(self, extra_formats=(), extra_ops=()):
        from tools.pstpu_lint.wire_registry import FORMATS, OPS

        return tuple(FORMATS) + tuple(extra_formats), \
            tuple(OPS) + tuple(extra_ops)

    def _tree(self, tmp_path, serde_extra=""):
        for rel in ("production_stack_tpu/kv_offload/serde.py",
                    "production_stack_tpu/kv_offload/remote.py",
                    "production_stack_tpu/kv_offload/server.py"):
            src = open(os.path.join(REPO, rel)).read()
            _write(tmp_path, rel, src)
        _write(tmp_path, "production_stack_tpu/disagg/transfer.py",
               open(os.path.join(
                   REPO, "production_stack_tpu/disagg/transfer.py")).read())
        _write(tmp_path, "production_stack_tpu/kv_offload/manager.py",
               'PREFIX = b"q8|"\n')
        _write(tmp_path, "native/kv_server.cpp",
               open(os.path.join(REPO, "native/kv_server.cpp")).read())
        if serde_extra:
            path = tmp_path / "production_stack_tpu/kv_offload/serde.py"
            path.write_text(path.read_text() + textwrap.dedent(serde_extra))

    def test_real_codecs_are_clean(self, tmp_path):
        from tools.pstpu_lint.rules.wire_drift import check_wire

        self._tree(tmp_path)
        assert check_wire(str(tmp_path), docs_check=False) == []

    def test_encoder_without_decoder_fires(self, tmp_path):
        from tools.pstpu_lint.rules.wire_drift import check_wire
        from tools.pstpu_lint.wire_registry import WireFormat

        self._tree(tmp_path, serde_extra="""
            _MAGIC_V3 = b"PKV3"


            def pack_block_v3(k, v):
                return struct.pack("<4s", _MAGIC_V3) + k.tobytes()
        """)
        formats, ops = self._registry(extra_formats=(
            WireFormat("PKV3", "kv-block", 3, "PKV2", False, "doc"),))
        findings = check_wire(str(tmp_path), registry_formats=formats,
                              registry_ops=ops, docs_check=False)
        assert [f.rule for f in findings] == ["PL010"]
        assert "no decoder" in findings[0].message
        assert findings[0].file.endswith("serde.py")

    def test_membership_test_counts_as_decoder(self, tmp_path):
        # A decoder spelled as a tuple-membership test is still a decoder.
        from tools.pstpu_lint.rules.wire_drift import check_wire
        from tools.pstpu_lint.wire_registry import WireFormat

        self._tree(tmp_path, serde_extra="""
            _MAGIC_V4 = b"PKV4"


            def pack_block_v4(k):
                return _MAGIC_V4 + k.tobytes()


            def sniff(blob):
                return blob[:4] in (_MAGIC_V4, b"PKV1")
        """)
        formats, ops = self._registry(extra_formats=(
            WireFormat("PKV4", "kv-block", 4, "PKV2", False, "doc"),))
        assert check_wire(str(tmp_path), registry_formats=formats,
                          registry_ops=ops, docs_check=False) == []

    def test_unregistered_magic_fires(self, tmp_path):
        from tools.pstpu_lint.rules.wire_drift import check_wire

        self._tree(tmp_path, serde_extra="""
            _MAGIC_V9 = b"PKV9"


            def unpack_block_v9(blob):
                if blob[:4] != _MAGIC_V9:
                    raise ValueError("nope")
                return blob[4:]


            def pack_block_v9(k):
                return _MAGIC_V9 + k.tobytes()
        """)
        findings = check_wire(str(tmp_path), docs_check=False)
        assert [f.rule for f in findings] == ["PL010"]
        assert "not in the wire registry" in findings[0].message

    def test_retired_format_with_encoder_fires(self, tmp_path):
        from tools.pstpu_lint.rules.wire_drift import check_wire
        from tools.pstpu_lint.wire_registry import FORMATS, OPS, WireFormat

        self._tree(tmp_path)
        formats = tuple(
            WireFormat(f.magic, f.family, f.version, f.supersedes,
                       True if f.magic == "PKV1" else f.retired, f.doc)
            for f in FORMATS
        )
        findings = check_wire(str(tmp_path), registry_formats=formats,
                              registry_ops=OPS, docs_check=False)
        assert any("retired" in f.message and "encoder" in f.message
                   for f in findings)

    def test_client_op_without_server_dispatch_fires(self, tmp_path):
        from tools.pstpu_lint.rules.wire_drift import check_wire
        from tools.pstpu_lint.wire_registry import WireOp

        self._tree(tmp_path)
        path = tmp_path / "production_stack_tpu/kv_offload/remote.py"
        path.write_text(path.read_text() + textwrap.dedent("""

            def flush(client):
                status, _ = client._request(b"F", b"")
                return status
        """))
        _formats, ops = self._registry(extra_ops=(
            WireOp("F", "flush", False, True, False, "doc"),))
        findings = check_wire(str(tmp_path), registry_ops=ops,
                              docs_check=False)
        assert [f.rule for f in findings] == ["PL010"]
        assert "never dispatches" in findings[0].message

    def test_native_coverage_mismatch_fires(self, tmp_path):
        from tools.pstpu_lint.rules.wire_drift import check_wire
        from tools.pstpu_lint.wire_registry import FORMATS, WireOp, OPS

        self._tree(tmp_path)
        ops = tuple(
            WireOp(o.op, o.name, o.batched, o.mutates,
                   True if o.op == "M" else o.native, o.doc)
            for o in OPS
        )
        findings = check_wire(str(tmp_path), registry_formats=FORMATS,
                              registry_ops=ops, docs_check=False)
        assert [f.rule for f in findings] == ["PL010"]
        assert "native" in findings[0].message


# ---------------------------------------------------- PL011/PL012/PL013
class TestHttpDrift:
    """HTTP control-surface drift against synthetic trees with injected
    registries (docs_check=False — the freshness leg is covered by the
    live gate and the stale-table probes below)."""

    def _header(self, name, producers, consumers, retired=False):
        from tools.pstpu_lint.http_registry import ProtocolHeader

        return ProtocolHeader(name, "request", tuple(producers),
                              tuple(consumers), "shape", retired, "doc")

    def _route(self, method, path, planes, debug=False, internal=False,
               test_ref=None):
        from tools.pstpu_lint.http_registry import Route

        return Route(method, path, tuple(planes), debug, internal,
                     test_ref, "doc")

    # ------------------------------------------------------------- PL011
    def test_registered_header_round_trip_is_clean(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_headers

        _write(tmp_path, "production_stack_tpu/router/proxy.py", """
            def forward():
                return {"x-pstpu-probe": "1"}
        """)
        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            def read(request):
                return request.headers.get("x-pstpu-probe")
        """)
        registry = (self._header("x-pstpu-probe", ("router",), ("engine",)),)
        assert check_headers(str(tmp_path), registry_headers=registry,
                             docs_check=False) == []

    def test_unregistered_header_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_headers

        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            def build():
                return {"x-pstpu-bogus": "1"}
        """)
        findings = check_headers(str(tmp_path), registry_headers=(),
                                 docs_check=False)
        assert _codes(findings) == ["PL011"]
        assert "x-pstpu-bogus" in findings[0].message
        assert "not in the HTTP registry" in findings[0].message
        assert findings[0].file == "production_stack_tpu/server/handlers.py"
        assert findings[0].line == 3

    def test_mixed_case_literal_fires_once(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_headers

        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            def read(request):
                return request.headers.get("X-Pstpu-Probe")
        """)
        registry = (self._header("x-pstpu-probe", ("external",),
                                 ("engine",)),)
        findings = check_headers(str(tmp_path), registry_headers=registry,
                                 docs_check=False)
        # Exactly one finding: the .get() arg is also a child of the Call
        # node, and the per-line dedupe must not double-report it.
        assert _codes(findings) == ["PL011"]
        assert "mixed-case" in findings[0].message
        assert "'x-pstpu-probe'" in findings[0].message

    def test_missing_consumer_plane_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_headers

        _write(tmp_path, "production_stack_tpu/router/proxy.py", """
            def forward():
                return {"x-pstpu-probe": "1"}
        """)
        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            def read(request):
                return None
        """)
        registry = (self._header("x-pstpu-probe", ("router",), ("engine",)),)
        findings = check_headers(str(tmp_path), registry_headers=registry,
                                 docs_check=False)
        assert _codes(findings) == ["PL011"]
        assert "no site in that plane reads it" in findings[0].message
        assert findings[0].file == "tools/pstpu_lint/http_registry.py"

    def test_missing_producer_plane_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_headers

        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            def read(request):
                return request.headers.get("x-pstpu-probe")
        """)
        registry = (self._header("x-pstpu-probe", ("router",), ("engine",)),)
        findings = check_headers(str(tmp_path), registry_headers=registry,
                                 docs_check=False)
        assert _codes(findings) == ["PL011"]
        assert "no site in that plane sets it" in findings[0].message

    def test_symbol_resolution_across_modules(self, tmp_path):
        # RESUME_HEADER-style shared constants: declared in one module,
        # produced and consumed by symbol name on different planes.
        from tools.pstpu_lint.rules.http_drift import check_headers

        _write(tmp_path, "production_stack_tpu/server/consts.py", """
            PROBE_HEADER = "x-pstpu-probe"
        """)
        _write(tmp_path, "production_stack_tpu/router/proxy.py", """
            from production_stack_tpu.server.consts import PROBE_HEADER

            def forward(headers):
                headers[PROBE_HEADER] = "1"
        """)
        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            from production_stack_tpu.server.consts import PROBE_HEADER

            def read(request):
                return request.headers.get(PROBE_HEADER)
        """)
        registry = (self._header("x-pstpu-probe", ("router",), ("engine",)),)
        assert check_headers(str(tmp_path), registry_headers=registry,
                             docs_check=False) == []

    def test_retired_header_reference_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_headers

        path = _write(tmp_path, "production_stack_tpu/server/handlers.py",
                      """
            def read(request):
                return request.headers.get("x-pstpu-old")
        """)
        registry = (self._header("x-pstpu-old", (), (), retired=True),)
        findings = check_headers(str(tmp_path), registry_headers=registry,
                                 docs_check=False)
        assert _codes(findings) == ["PL011"]
        assert "retired" in findings[0].message

        # A lingering declaration alone is fine (the constant may stay
        # for migration tooling); only live references fire.
        path.write_text('OLD_HEADER = "x-pstpu-old"\n')
        assert check_headers(str(tmp_path), registry_headers=registry,
                             docs_check=False) == []

    def test_docstring_mention_is_not_a_site(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_headers

        _write(tmp_path, "production_stack_tpu/server/handlers.py", '''
            """Speaks "x-pstpu-bogus" in prose only."""
        ''')
        assert check_headers(str(tmp_path), registry_headers=(),
                             docs_check=False) == []

    def test_payload_key_missing_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_headers

        # A registered pstpu-payload consumer that stopped speaking one
        # of the keys: the chunk shape drifted.
        _write(tmp_path, "production_stack_tpu/router/sse.py", """
            def parse(chunk):
                state = chunk.get("pstpu", {})
                return state.get("toks", []), state.get("off", 0)
        """)
        findings = check_headers(str(tmp_path), registry_headers=(),
                                 docs_check=False)
        assert _codes(findings) == ["PL011"]
        assert "'seed'" in findings[0].message
        assert findings[0].file == "production_stack_tpu/router/sse.py"

    # ------------------------------------------------------------- PL012
    def test_registered_route_is_clean(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_routes

        _write(tmp_path, "production_stack_tpu/router/app.py", """
            def build_app(app, h):
                app.router.add_post("/v1/probe", h)
        """)
        _write(tmp_path, "tests/test_probe.py", """
            URL = "/v1/probe"
        """)
        registry = (self._route("POST", "/v1/probe", ("router",)),)
        assert check_routes(str(tmp_path), registry_routes=registry,
                            docs_check=False) == []

    def test_unregistered_route_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_routes

        _write(tmp_path, "production_stack_tpu/router/app.py", """
            def build_app(app, h):
                app.router.add_get("/v1/bogus", h)
        """)
        findings = check_routes(str(tmp_path), registry_routes=(),
                                docs_check=False)
        assert _codes(findings) == ["PL012"]
        assert "GET /v1/bogus" in findings[0].message
        assert "not in the HTTP registry" in findings[0].message
        assert findings[0].file == "production_stack_tpu/router/app.py"
        assert findings[0].line == 3

    def test_unserved_registered_route_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_routes

        _write(tmp_path, "production_stack_tpu/router/app.py", """
            def build_app(app, h):
                pass
        """)
        _write(tmp_path, "tests/test_probe.py", 'URL = "/v1/probe"\n')
        registry = (self._route("POST", "/v1/probe", ("router",)),)
        findings = check_routes(str(tmp_path), registry_routes=registry,
                                docs_check=False)
        assert _codes(findings) == ["PL012"]
        assert "not served by the 'router' plane" in findings[0].message

    def test_debug_route_outside_gate_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_routes

        _write(tmp_path, "production_stack_tpu/server/api_server.py", """
            def build_app(self, app):
                app.router.add_get("/debug/probe", self.h)
        """)
        _write(tmp_path, "tests/test_probe.py", 'URL = "/debug/probe"\n')
        registry = (self._route("GET", "/debug/probe", ("engine",),
                                debug=True),)
        findings = check_routes(str(tmp_path), registry_routes=registry,
                                docs_check=False)
        assert _codes(findings) == ["PL012"]
        assert "debug_endpoints" in findings[0].message

        # Behind the gate it is clean — and the inverse (an always-on
        # route served under the gate) fires the other direction.
        _write(tmp_path, "production_stack_tpu/server/api_server.py", """
            def build_app(self, app):
                if self.engine.config.debug_endpoints:
                    app.router.add_get("/debug/probe", self.h)
        """)
        assert check_routes(str(tmp_path), registry_routes=registry,
                            docs_check=False) == []
        always_on = (self._route("GET", "/debug/probe", ("engine",)),)
        findings = check_routes(str(tmp_path), registry_routes=always_on,
                                docs_check=False)
        assert _codes(findings) == ["PL012"]
        assert "registered as always-on" in findings[0].message

    def test_untested_route_fires_and_internal_is_exempt(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_routes

        _write(tmp_path, "production_stack_tpu/router/app.py", """
            def build_app(app, h):
                app.router.add_post("/v1/probe", h)
        """)
        _write(tmp_path, "tests/test_other.py", 'X = 1\n')
        registry = (self._route("POST", "/v1/probe", ("router",)),)
        findings = check_routes(str(tmp_path), registry_routes=registry,
                                docs_check=False)
        assert _codes(findings) == ["PL012"]
        assert "referenced by no file under tests/" in findings[0].message

        internal = (self._route("POST", "/v1/probe", ("router",),
                                internal=True),)
        assert check_routes(str(tmp_path), registry_routes=internal,
                            docs_check=False) == []

    # ------------------------------------------------------------- PL013
    def test_503_with_retry_after_is_clean(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_status

        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            from aiohttp import web

            def shed():
                return web.json_response(
                    {"status": "shedding"}, status=503,
                    headers={"Retry-After": "1"},
                )
        """)
        assert check_status(str(tmp_path), docs_check=False) == []

    def test_503_without_retry_after_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_status

        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            from aiohttp import web

            def shed():
                return web.json_response({"status": "shedding"}, status=503)
        """)
        findings = check_status(str(tmp_path), docs_check=False)
        assert _codes(findings) == ["PL013"]
        assert "'retry-after'" in findings[0].message
        assert findings[0].line == 5

    def test_error_helper_503_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_status

        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            def shed(_error):
                return _error(503, "queue full")
        """)
        findings = check_status(str(tmp_path), docs_check=False)
        assert _codes(findings) == ["PL013"]
        assert "503" in findings[0].message

    def test_server_emitting_client_marker_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_status

        _write(tmp_path, "production_stack_tpu/router/app.py", """
            from aiohttp import web

            def nope():
                return web.Response(status=599)
        """)
        findings = check_status(str(tmp_path), docs_check=False)
        assert _codes(findings) == ["PL013"]
        assert "client-side" in findings[0].message

        # The bench plane OWNS the 599 marker — same code there is clean.
        _write(tmp_path, "production_stack_tpu/router/app.py", "X = 1\n")
        _write(tmp_path, "benchmarks/client.py", """
            def mark_truncated(record):
                record["status"] = 599
                return record
        """)
        assert check_status(str(tmp_path), docs_check=False) == []

    def test_unregistered_status_fires(self, tmp_path):
        from tools.pstpu_lint.rules.http_drift import check_status

        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            from aiohttp import web

            def teapot():
                return web.json_response({}, status=418)
        """)
        findings = check_status(str(tmp_path), docs_check=False)
        assert _codes(findings) == ["PL013"]
        assert "418" in findings[0].message
        assert "not in the HTTP registry" in findings[0].message

    def test_dynamic_sites_are_out_of_scope(self, tmp_path):
        # Non-literal headers kwarg: unverifiable, treated as satisfied.
        # Non-constant status (the fake engine's fault injection): skipped.
        from tools.pstpu_lint.rules.http_drift import check_status

        _write(tmp_path, "production_stack_tpu/server/handlers.py", """
            from aiohttp import web

            def shed(hdrs):
                return web.json_response({}, status=503, headers=hdrs)

            def fault(self):
                return web.json_response({}, status=self.unavailable_status)
        """)
        assert check_status(str(tmp_path), docs_check=False) == []


# ------------------------------------------------------------ PL006 helm leg
class TestHelmDrift:
    def _chart(self, tmp_path, flag="--num-decode-steps",
               schema_keys=("numDecodeSteps",),
               template_keys=("numDecodeSteps",)):
        _write(tmp_path, "production_stack_tpu/server/api_server.py", """
            import argparse

            def parse_args():
                p = argparse.ArgumentParser()
                p.add_argument("--num-decode-steps", type=int, default=8,
                               help="fused decode steps")
                return p.parse_args()

            def main(args):
                print(args.num_decode_steps)
        """)
        _write(tmp_path, "production_stack_tpu/router/parser.py", """
            import argparse

            def parse_args():
                p = argparse.ArgumentParser()
                p.add_argument("--routing-logic", default="roundrobin",
                               help="routing policy")
                return p.parse_args()
        """)
        args = "\n".join(
            f'            - "{flag}"\n'
            f"            - {{{{ $modelSpec.tpuConfig.{k} | quote }}}}"
            for k in template_keys
        )
        _write(tmp_path, "helm/templates/deployment-engine.yaml",
               "spec:\n  template:\n    spec:\n      containers:\n"
               "        - args:\n" + args + "\n")
        import json as _json

        schema = {
            "properties": {
                "servingEngineSpec": {"properties": {"modelSpec": {
                    "items": {"properties": {"tpuConfig": {
                        "properties": {k: {} for k in schema_keys}
                    }}}
                }}},
                "routerSpec": {"properties": {}},
            }
        }
        _write(tmp_path, "helm/values.schema.json", _json.dumps(schema))
        _write(tmp_path, "helm/values.yaml", "servingEngineSpec:\n")

    def test_clean_chart_passes(self, tmp_path):
        from tools.pstpu_lint.rules.flag_drift import check_helm

        self._chart(tmp_path)
        assert check_helm(str(tmp_path)) == []

    def test_dead_helm_knob_fires(self, tmp_path):
        # The template renders a flag the engine parser does not define.
        from tools.pstpu_lint.rules.flag_drift import check_helm

        self._chart(tmp_path, flag="--num-decode-stepz")
        findings = check_helm(str(tmp_path))
        assert [f.rule for f in findings] == ["PL006"]
        assert "--num-decode-stepz" in findings[0].message
        assert "does not exist" in findings[0].message
        assert findings[0].file.endswith("deployment-engine.yaml")

    def test_key_missing_from_schema_fires(self, tmp_path):
        from tools.pstpu_lint.rules.flag_drift import check_helm

        self._chart(tmp_path, schema_keys=())
        findings = check_helm(str(tmp_path))
        assert [f.rule for f in findings] == ["PL006"]
        assert "not declared" in findings[0].message

    def test_schema_key_no_template_consumes_fires(self, tmp_path):
        from tools.pstpu_lint.rules.flag_drift import check_helm

        self._chart(tmp_path,
                    schema_keys=("numDecodeSteps", "ghostKnob"))
        findings = check_helm(str(tmp_path))
        assert [f.rule for f in findings] == ["PL006"]
        assert "ghostKnob" in findings[0].message
        assert "no template" in findings[0].message

    def test_values_key_missing_from_schema_fires(self, tmp_path):
        from tools.pstpu_lint.rules.flag_drift import check_helm

        self._chart(tmp_path)
        _write(tmp_path, "helm/values.yaml", """
            routerSpec:
              routingLogic: "roundrobin"
        """)
        findings = check_helm(str(tmp_path))
        assert [f.rule for f in findings] == ["PL006"]
        assert "routerSpec.routingLogic" in findings[0].message
        assert "missing from" in findings[0].message

    def test_live_chart_is_covered(self):
        # The real chart parses and the scanner finds the known wirings —
        # guards the regexes against template drift.
        from tools.pstpu_lint.flags import scan_helm_wirings

        with open(os.path.join(
                REPO, "helm/templates/deployment-engine.yaml")) as f:
            wirings = scan_helm_wirings(f.read())
        by_key = {w.key: w.flag for w in wirings if w.section == "tpuConfig"}
        assert by_key.get("tensorParallelSize") == "--tensor-parallel-size"
        assert by_key.get("kvCacheDtype") == "--kv-cache-dtype"
        # accelerator is nodeSelector wiring, not a flag
        assert by_key.get("accelerator") is None


# -------------------------------------------------------------------- waivers
class TestWaivers:
    def test_waiver_with_reason_suppresses(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import time

            async def handler(request):
                time.sleep(0.01)  # pstpu-lint: allow[PL001] reason=test probe
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_trailing_waiver_on_wrapped_statement_suppresses(self, tmp_path):
        # The finding anchors at the call's first line; a comment trailing
        # the closing-paren line must anchor to the logical-line START.
        _write(tmp_path, ROUTER_FILE, """
            import time

            async def handler(request):
                time.sleep(
                    0.01
                )  # pstpu-lint: allow[PL001] reason=test probe
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_standalone_waiver_line_anchors_to_next_code_line(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import time

            async def handler(request):
                # pstpu-lint: allow[PL001] reason=test probe
                time.sleep(0.01)
        """)
        assert _lint(tmp_path, ROUTER_FILE) == []

    def test_reasonless_waiver_is_pl000(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            import time

            async def handler(request):
                time.sleep(0.01)  # pstpu-lint: allow[PL001]
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        # The finding is suppressed, but the reason-less waiver is an error.
        assert _codes(findings) == ["PL000"]
        assert "no reason" in findings[0].message

    def test_stale_waiver_is_pl000(self, tmp_path):
        _write(tmp_path, ROUTER_FILE, """
            async def handler(request):
                return 1  # pstpu-lint: allow[PL001] reason=left over
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL000"]
        assert "suppresses nothing" in findings[0].message

    def test_parse_waivers_multi_rule(self):
        src = "x = 1  # pstpu-lint: allow[PL001,PL003] reason=why not\n"
        (w,) = parse_waivers("f.py", src)
        assert w.rules == ("PL001", "PL003")
        assert w.reason == "why not"
        assert w.anchor_line == 1

    def test_unknown_rule_code_is_pl000(self, tmp_path):
        # A waiver naming a rule that does not exist (typo, or a code left
        # behind by a rename) is an error, not a silent no-op — and it is
        # NOT double-reported as stale.
        _write(tmp_path, ROUTER_FILE, """
            x = 1  # pstpu-lint: allow[PL999] reason=renamed long ago
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL000"]
        assert "unknown rule" in findings[0].message
        assert "PL999" in findings[0].message

    def test_known_plus_unknown_rule_mix(self, tmp_path):
        # The known half still suppresses; only the unknown half errors.
        _write(tmp_path, ROUTER_FILE, """
            import time

            async def handler(request):
                time.sleep(0.01)  # pstpu-lint: allow[PL001,PL998] reason=x
        """)
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL000"]
        assert "allow[PL998]" in findings[0].message
        assert "unknown rule" in findings[0].message

    def test_new_rule_codes_are_waivable(self, tmp_path):
        # The PL007-PL010 codes ride the same PL000 machinery.
        _write(tmp_path, ENGINE_FILE, """
            import jax

            @jax.jit
            def step(x):
                return x.item()  # pstpu-lint: allow[PL008] reason=debug shim
        """)
        assert _lint(tmp_path, ENGINE_FILE) == []


# ------------------------------------------------------------------ reporting
class TestReporting:
    def test_github_annotation_format(self):
        f = Finding("PL001", "production_stack_tpu/router/app.py", 12,
                    "time.sleep() blocks the event loop")
        out = f.render("github")
        assert out.startswith(
            "::error file=production_stack_tpu/router/app.py,line=12,"
        )
        assert "PL001" in out and "time.sleep" in out

    def test_malformed_file_is_a_finding_not_a_crash(self, tmp_path):
        # IndentationError escapes tokenize; the run must survive with a
        # PL000 finding, not abort and lose every other file's findings.
        _write(tmp_path, ROUTER_FILE,
               "def f():\n        x = 1\n    y = 2\n")
        findings = _lint(tmp_path, ROUTER_FILE)
        assert _codes(findings) == ["PL000"]
        assert "does not parse" in findings[0].message

    def test_cli_exit_codes(self, tmp_path, capsys):
        _write(tmp_path, ROUTER_FILE, """
            import time

            async def handler(request):
                time.sleep(0.01)
        """)
        rc = main([str(tmp_path / ROUTER_FILE),
                   "--project-root", str(tmp_path),
                   "--no-project-rules", "--format", "github"])
        assert rc == 1
        assert "::error file=" in capsys.readouterr().out

        (tmp_path / ROUTER_FILE).write_text("x = 1\n")
        rc = main([str(tmp_path / ROUTER_FILE),
                   "--project-root", str(tmp_path), "--no-project-rules"])
        assert rc == 0


# ------------------------------------------------------------------ the gate
class TestLiveRepo:
    def test_repo_lints_clean(self):
        """The acceptance gate: the real tree has zero findings (and so
        zero reason-less or stale waivers). A new violation fails tier-1
        here, not just the CI lint job."""
        findings = run_lint(
            [os.path.join(REPO, p)
             for p in ("production_stack_tpu", "tools", "benchmarks")],
            project_root=REPO,
        )
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_docs_tables_are_fresh(self):
        """docs/METRICS.md + the focused tables + README flag tables +
        docs/WIRE_FORMATS.md + docs/HTTP_PROTOCOL.md (and the status/
        resume tables it feeds) match the registries (regenerate with
        python -m tools.pstpu_lint.gen_docs)."""
        from tools.pstpu_lint.gen_docs import (
            check_flag_tables,
            check_http_tables,
            check_tables,
            check_wire_tables,
        )

        assert check_tables(REPO) == []
        assert check_flag_tables(REPO) == []
        assert check_wire_tables(REPO) == []
        assert check_http_tables(REPO) == []

    def test_stale_wire_table_fails_pl010(self, tmp_path):
        """The PL010 docs-freshness gate, PL004-style: a WIRE_FORMATS.md
        whose table no longer matches the registry is a finding."""
        import shutil

        from tools.pstpu_lint.rules.wire_drift import check_wire

        for rel in ("production_stack_tpu/kv_offload",
                    "production_stack_tpu/disagg", "native"):
            shutil.copytree(os.path.join(REPO, rel), tmp_path / rel)
        docs = open(os.path.join(REPO, "docs/WIRE_FORMATS.md")).read()
        _write(tmp_path, "docs/WIRE_FORMATS.md",
               docs.replace("| `PKV2` |", "| `PKV9` |"))
        findings = check_wire(str(tmp_path))
        assert [f.rule for f in findings] == ["PL010"]
        assert "out of date" in findings[0].message
        assert findings[0].file == "docs/WIRE_FORMATS.md"

    def test_deliberate_violation_fails(self, tmp_path):
        """The CI acceptance probe: introducing a time.sleep in an async
        def in the router makes the lint fail with a file/line finding."""
        bad = _write(tmp_path, ROUTER_FILE, """
            import time

            async def handle_completions(request):
                time.sleep(1)
        """)
        findings = run_lint([str(bad)], project_root=str(tmp_path),
                            project_rules=False)
        assert [f.rule for f in findings] == ["PL001"]
        assert findings[0].line == 5


class TestLiveRepoInjections:
    """The acceptance probes: each hazard injected into a COPY of the
    real source must fail the suite with a correct file/line github
    annotation. These guard the analyzers themselves — a rule that
    silently stops firing on the real tree's idioms fails here."""

    # Everything the HTTP drift rules scan: sources + the test-reference
    # corpus + the registry (finding anchors) + the generated docs.
    HTTP_DIRS = ("production_stack_tpu", "benchmarks", "tests", "tools",
                 "docs")

    def _copy(self, tmp_path, rel):
        src = open(os.path.join(REPO, rel)).read()
        return src, tmp_path / rel

    def _http_tree(self, tmp_path):
        import shutil

        for rel in self.HTTP_DIRS:
            shutil.copytree(os.path.join(REPO, rel), tmp_path / rel,
                            ignore=shutil.ignore_patterns("__pycache__"))

    def _annotations(self, findings):
        return [f.render("github") for f in findings]

    def test_use_after_donate_in_runner(self, tmp_path):
        """(a) a read of a donated pool binding after the decode dispatch
        in runner.py fires PL007 at the injected line."""
        rel = "production_stack_tpu/engine/runner.py"
        src, _path = self._copy(tmp_path, rel)
        needle = ("        self._rebind_scale_pools(kv_ks2, kv_vs2)\n"
                  "        self._rebind_spec_pools(sp_k2, sp_v2, sp_p2)\n"
                  "        if self.kv_quantized:")
        assert src.count(needle) >= 1, "decode dispatch idiom moved"
        injected = needle.replace(
            "        if self.kv_quantized:",
            "        stale = wk.sum()  # injected use-after-donate\n"
            "        if self.kv_quantized:")
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src.replace(needle, injected, 1))
        line = src[:src.index(needle)].count("\n") + 3
        findings = run_lint([str(path)], project_root=str(tmp_path),
                            project_rules=False)
        assert [f.rule for f in findings] == ["PL007"]
        assert findings[0].line == line
        ann = self._annotations(findings)[0]
        assert ann.startswith(f"::error file={rel},line={line},")
        assert "PL007" in ann

        # Control: the unmodified runner.py is clean (the rebind idiom is
        # the checked contract, not a waiver).
        path.write_text(src)
        assert run_lint([str(path)], project_root=str(tmp_path),
                        project_rules=False) == []

    def test_item_in_fused_decode_scan(self, tmp_path):
        """(b) an .item() inside the fused decode scan body fires PL008."""
        rel = "production_stack_tpu/engine/runner.py"
        src, _ = self._copy(tmp_path, rel)
        needle = ("            def scan_body(carry, j):\n"
                  "                carry, nxt, lp = body(carry, j)\n")
        assert src.count(needle) == 1, "fused decode scan body moved"
        injected = needle + \
            "                probe = nxt.item()  # injected host sync\n"
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src.replace(needle, injected))
        line = src[:src.index(needle)].count("\n") + 3
        findings = run_lint([str(path)], project_root=str(tmp_path),
                            project_rules=False)
        assert [f.rule for f in findings] == ["PL008"]
        assert findings[0].line == line
        assert ".item()" in findings[0].message
        ann = self._annotations(findings)[0]
        assert ann.startswith(f"::error file={rel},line={line},")

    def test_unlocked_counter_in_engine_stats(self, tmp_path):
        """(c) an unlocked cross-thread mutation of scraper state in
        engine_stats.py fires PL009."""
        rel = "production_stack_tpu/router/stats/engine_stats.py"
        src, _ = self._copy(tmp_path, rel)
        needle = "        live = {ep.url for ep in endpoints}\n"
        assert src.count(needle) == 1, "scrape pass shape moved"
        injected = needle + ("        self.engine_stats[\"__passes__\"] = "
                             "EngineStats()  # injected unlocked\n")
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src.replace(needle, injected))
        line = src[:src.index(needle)].count("\n") + 2
        findings = run_lint([str(path)], project_root=str(tmp_path),
                            project_rules=False)
        assert [f.rule for f in findings] == ["PL009"]
        assert findings[0].line == line
        assert "without the lock" in findings[0].message
        ann = self._annotations(findings)[0]
        assert ann.startswith(f"::error file={rel},line={line},")

        path.write_text(src)
        assert run_lint([str(path)], project_root=str(tmp_path),
                        project_rules=False) == []

    def test_pkv3_encoder_without_decoder(self, tmp_path):
        """(d) a new PKV3 encoder with no decoder fires PL010 at the
        encoder site in serde.py."""
        import shutil

        from tools.pstpu_lint.rules.wire_drift import check_wire

        for rel in ("production_stack_tpu/kv_offload",
                    "production_stack_tpu/disagg", "native"):
            shutil.copytree(os.path.join(REPO, rel), tmp_path / rel)
        serde = tmp_path / "production_stack_tpu/kv_offload/serde.py"
        src = serde.read_text()
        serde.write_text(src + textwrap.dedent("""

            _MAGIC_V3 = b"PKV3"


            def pack_block_v3(k, v):
                return struct.pack("<4s", _MAGIC_V3) + k.tobytes()
        """))
        findings = check_wire(str(tmp_path), docs_check=False)
        rules = sorted({f.rule for f in findings})
        assert rules == ["PL010"]
        msgs = " | ".join(f.message for f in findings)
        assert "PKV3" in msgs
        assert "no decoder" in msgs
        rel = "production_stack_tpu/kv_offload/serde.py"
        assert all(f.file == rel for f in findings)
        assert all(f.line > src.count("\n") for f in findings)
        ann = self._annotations(findings)[0]
        assert ann.startswith(f"::error file={rel},line=")

        # Control: the pristine copy is clean.
        serde.write_text(src)
        assert check_wire(str(tmp_path), docs_check=False) == []

    def test_bogus_header_in_request_service(self, tmp_path):
        """(e) an unregistered x-pstpu-* header set in a copy of the
        router's proxy path fires PL011 at the injected line."""
        from tools.pstpu_lint.rules.http_drift import check_headers

        self._http_tree(tmp_path)
        rel = "production_stack_tpu/router/request_service.py"
        path = tmp_path / rel
        src = path.read_text()
        needle = ('    headers[DISAGG_FALLBACK_HEADER] = "1"\n'
                  '    headers[RESUME_HEADER] = "1"\n')
        assert src.count(needle) == 1, "resume header synthesis moved"
        path.write_text(src.replace(
            needle, needle + '    headers["x-pstpu-bogus"] = "1"\n'))
        line = src[:src.index(needle)].count("\n") + 3
        findings = check_headers(str(tmp_path), docs_check=False)
        assert _codes(findings) == ["PL011"]
        assert findings[0].line == line
        assert "x-pstpu-bogus" in findings[0].message
        ann = self._annotations(findings)[0]
        assert ann.startswith(f"::error file={rel},line={line},")

        # Control: the pristine copy is clean.
        path.write_text(src)
        assert check_headers(str(tmp_path), docs_check=False) == []

    def test_bogus_route_in_api_server(self, tmp_path):
        """(f) an unregistered route registration in a copy of the engine
        API server fires PL012 at the add_get line."""
        from tools.pstpu_lint.rules.http_drift import check_routes

        self._http_tree(tmp_path)
        rel = "production_stack_tpu/server/api_server.py"
        path = tmp_path / rel
        src = path.read_text()
        needle = '        app.router.add_get("/version", self.version)\n'
        assert src.count(needle) == 1, "route table shape moved"
        path.write_text(src.replace(
            needle,
            needle + '        app.router.add_get("/v1/bogus", self.version)\n'
        ))
        line = src[:src.index(needle)].count("\n") + 2
        findings = check_routes(str(tmp_path), docs_check=False)
        assert _codes(findings) == ["PL012"]
        assert findings[0].line == line
        assert "GET /v1/bogus" in findings[0].message
        ann = self._annotations(findings)[0]
        assert ann.startswith(f"::error file={rel},line={line},")

        path.write_text(src)
        assert check_routes(str(tmp_path), docs_check=False) == []

    def test_retry_after_less_503_in_api_server(self, tmp_path):
        """(g) stripping Retry-After from a real 503 emit site in a copy
        of the engine API server fires PL013 at that site."""
        from tools.pstpu_lint.rules.http_drift import check_status

        self._http_tree(tmp_path)
        rel = "production_stack_tpu/server/api_server.py"
        path = tmp_path / rel
        src = path.read_text()
        needle = (
            '            return _error(503, f"Profiler failed to start: '
            '{e}",\n'
            '                          etype="service_unavailable",\n'
            '                          headers={"Retry-After": "1"})\n')
        assert src.count(needle) == 1, "profiler 503 site moved"
        path.write_text(src.replace(
            needle,
            '            return _error(503, f"Profiler failed to start: '
            '{e}",\n'
            '                          etype="service_unavailable")\n'))
        line = src[:src.index(needle)].count("\n") + 1
        findings = check_status(str(tmp_path), docs_check=False)
        assert _codes(findings) == ["PL013"]
        assert findings[0].line == line
        assert "'retry-after'" in findings[0].message
        ann = self._annotations(findings)[0]
        assert ann.startswith(f"::error file={rel},line={line},")

        path.write_text(src)
        assert check_status(str(tmp_path), docs_check=False) == []

    def test_stale_http_doc_fails_pl011(self, tmp_path):
        """A doctored docs/HTTP_PROTOCOL.md headers table is a PL011
        finding pointing at the docs file (the PL004-style freshness
        gate for the HTTP tables)."""
        from tools.pstpu_lint.rules.http_drift import check_headers

        self._http_tree(tmp_path)
        doc = tmp_path / "docs/HTTP_PROTOCOL.md"
        doc.write_text(doc.read_text().replace(
            "| `x-pstpu-resume` |", "| `x-pstpu-resumed` |"))
        findings = check_headers(str(tmp_path))
        assert _codes(findings) == ["PL011"]
        assert "out of date" in findings[0].message
        assert findings[0].file == "docs/HTTP_PROTOCOL.md"
