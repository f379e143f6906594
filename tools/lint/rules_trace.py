"""Family (a): JAX trace/sync hygiene — hot-path host syncs and recompile
hazards. Scoped to the serving hot paths (engine/, ops/, models/): a stray
`.item()` there stalls the fused decode pipeline for every tenant, and one
tracer-dependent Python branch recompiles a program we promise compiles
exactly once (see the compile-count tripwire in localai_tpu/testing)."""
from __future__ import annotations

import ast

from tools.lint.astutil import (
    DeviceTracker, call_name, collect_jit_info, dotted, expr_mentions_device,
    is_device_call, last_segment,
)
from tools.lint.core import Violation


def _functions(tree):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class HostSyncItem:
    name = "host-sync-item"
    family = "trace"
    description = (".item() in a hot path — an implicit device→host sync "
                   "that stalls the decode pipeline")

    def check(self, ctx):
        if not ctx.config.in_hot_path(ctx.path):
            return
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                yield Violation(
                    ctx.path, node.lineno, self.name,
                    ".item() forces a device→host sync; keep the value on "
                    "device, or jax.device_get() a batch of results once")


class HostSyncCast:
    name = "host-sync-cast"
    family = "trace"
    description = ("float()/int()/bool() on a device value in a hot path — "
                   "implicit device→host sync")

    def check(self, ctx):
        if not ctx.config.in_hot_path(ctx.path):
            return
        _, jit_callables = collect_jit_info(ctx.tree)
        jit_names = set(jit_callables)
        for fn in _functions(ctx.tree):
            tracker = DeviceTracker(fn, jit_names)
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id in ("float", "int", "bool")
                        and len(node.args) == 1):
                    continue
                if expr_mentions_device(node.args[0], tracker, ctx.parents,
                                        node.lineno):
                    yield Violation(
                        ctx.path, node.lineno, self.name,
                        f"{node.func.id}() on a device value blocks on the "
                        f"device — fetch once via jax.device_get() and cast "
                        f"the host copy")


class HostSyncAsarray:
    name = "host-sync-asarray"
    family = "trace"
    description = ("np.asarray()/np.array() on a device value in a hot "
                   "path — implicit device→host transfer")

    _NP = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}

    def check(self, ctx):
        if not ctx.config.in_hot_path(ctx.path):
            return
        _, jit_callables = collect_jit_info(ctx.tree)
        jit_names = set(jit_callables)
        for fn in _functions(ctx.tree):
            tracker = DeviceTracker(fn, jit_names)
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and call_name(node) in self._NP and node.args):
                    continue
                if expr_mentions_device(node.args[0], tracker, ctx.parents,
                                        node.lineno):
                    yield Violation(
                        ctx.path, node.lineno, self.name,
                        "np.asarray on a device value is an implicit "
                        "device→host transfer — spell the sync explicitly "
                        "with jax.device_get()")


class SyncBlockUntilReady:
    name = "sync-block-until-ready"
    family = "trace"
    description = ("block_until_ready() in a hot path — defeats the decode "
                   "pipeline (one in-flight dispatch)")

    def check(self, ctx):
        if not ctx.config.in_hot_path(ctx.path):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            is_method = (isinstance(node.func, ast.Attribute)
                         and node.func.attr == "block_until_ready")
            if name == "jax.block_until_ready" or is_method:
                yield Violation(
                    ctx.path, node.lineno, self.name,
                    "block_until_ready fences the dispatch pipeline; hot "
                    "paths must stay async — fence only in startup probes")


class TracedBranch:
    name = "traced-branch"
    family = "trace"
    description = ("Python if/while on a jit-traced value — recompiles per "
                   "trace or raises TracerBoolConversionError")

    def check(self, ctx):
        jitted_funcs, _ = collect_jit_info(ctx.tree)
        if not jitted_funcs:
            return
        for fn in _functions(ctx.tree):
            statics = jitted_funcs.get(fn.name)
            if statics is None:
                continue
            args = ([a.arg for a in fn.args.posonlyargs]
                    + [a.arg for a in fn.args.args]
                    + [a.arg for a in fn.args.kwonlyargs])
            traced = set()
            for i, a in enumerate(args):
                if a in statics or i in statics:
                    continue
                # project conventions for non-array params
                if a in ("self", "cfg", "config", "mesh", "econfig"):
                    continue
                traced.add(a)
            if not traced:
                continue
            # propagate through straight-line assignments from traced values
            derived = set(traced)
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign):
                    mentions = any(
                        isinstance(n, ast.Name) and n.id in derived
                        and not _meta_only(n, node.value, ctx.parents)
                        for n in ast.walk(node.value))
                    if mentions:
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                derived.add(t.id)
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                hit = self._naked_traced_name(node.test, derived, ctx)
                if hit:
                    kind = "while" if isinstance(node, ast.While) else "if"
                    yield Violation(
                        ctx.path, node.lineno, self.name,
                        f"`{kind}` on traced value {hit!r} inside jitted "
                        f"{fn.name}() — use jnp.where/lax.cond, or mark the "
                        f"argument static")

    @staticmethod
    def _naked_traced_name(test: ast.AST, traced: set[str], ctx):
        from tools.lint.astutil import _is_shielded

        for n in ast.walk(test):
            if isinstance(n, ast.Name) and n.id in traced:
                if not _is_shielded(n, test, ctx.parents):
                    return n.id
        return None


def _meta_only(name_node, stop, parents):
    from tools.lint.astutil import _is_shielded

    return _is_shielded(name_node, stop, parents)


class JitArgRetrace:
    name = "jit-arg-retrace"
    family = "trace"
    description = ("argument type at a jit boundary defeats caching — lists/"
                   "generators retrace per length, bare len() retraces per "
                   "value")

    def check(self, ctx):
        _, jit_callables = collect_jit_info(ctx.tree)
        if not jit_callables:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            seg = last_segment(node.func)
            if seg not in jit_callables:
                continue
            statics = jit_callables[seg]
            candidates = [(None, a) for a in node.args] + [
                (kw.arg, kw.value) for kw in node.keywords
                if kw.arg not in statics]
            for kwname, arg in candidates:
                bad = self._bad_kind(arg)
                if bad:
                    where = f"keyword {kwname!r}" if kwname else "argument"
                    yield Violation(
                        ctx.path, arg.lineno, self.name,
                        f"{where} to jitted {seg!r} is {bad} — every "
                        f"distinct length/value compiles a new program; "
                        f"wrap in jnp.asarray / np.asarray or declare it "
                        f"in static_argnames")

    @staticmethod
    def _bad_kind(arg: ast.AST) -> str | None:
        if isinstance(arg, (ast.List, ast.ListComp, ast.Set, ast.SetComp,
                            ast.GeneratorExp)):
            return "a Python list/set/generator (variable-length pytree)"
        if isinstance(arg, ast.Call) and dotted(arg.func) == "len":
            return "a bare len() (a fresh Python int per call)"
        return None


class ShapeFromLen:
    name = "shape-from-len"
    family = "trace"
    description = ("array constructor shaped by len(data) in a hot path — "
                   "a data-dependent shape recompiles per request")

    _CTORS = {"zeros", "ones", "full", "empty", "arange"}

    def check(self, ctx):
        if not ctx.config.in_hot_path(ctx.path):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if not name or "." not in name:
                continue
            root, _, fn = name.rpartition(".")
            if root not in ("jnp", "jax.numpy") or fn not in self._CTORS:
                continue
            shape_args = list(node.args[:1]) + [
                kw.value for kw in node.keywords if kw.arg == "shape"]
            for arg in shape_args:
                for sub in ast.walk(arg):
                    if (isinstance(sub, ast.Call)
                            and dotted(sub.func) == "len"):
                        yield Violation(
                            ctx.path, node.lineno, self.name,
                            f"jnp.{fn} shaped by len(...) — pad to a fixed "
                            f"bucket instead (prefill_buckets pattern); "
                            f"data-dependent shapes recompile per request")
                        break


RULES = [HostSyncItem(), HostSyncCast(), HostSyncAsarray(),
         SyncBlockUntilReady(), TracedBranch(), JitArgRetrace(),
         ShapeFromLen()]
