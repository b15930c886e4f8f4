"""AST rules RA001-RA006.

Each rule is grounded in a bug class this repo has actually hit; see
ANALYSIS.md for the incident behind every rule ID.  The checker is pure
stdlib (``ast`` only) so the lint layer never pays a jax import.

Scope machinery
---------------
Several rules only apply *inside traced code* — function bodies that run
under ``jax.jit`` / ``lax.scan`` / ``vmap`` et al.  Tracedness is
approximated per module:

* a function is traced if a decorator resolves to a tracing transform
  (``@jax.jit``, ``@partial(jax.jit, ...)``, ...), or
* its name is passed to a tracing call anywhere in the module
  (``lax.scan(step, ...)``, ``jax.jit(run)``, including through
  ``functools.partial`` and nested transforms), and
* every function/lambda nested inside a traced function is traced (it
  executes during the trace).

Name resolution follows import aliases (``import jax.numpy as jnp``,
``from jax import lax``), so the rules match the canonical dotted path,
not the surface spelling.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str
    # The stripped source line, used for line-number-independent baseline
    # hashes (see repro.analysis.baseline).
    source_line: str = ""

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col + 1}: {self.rule} {self.message}"


RULES: dict[str, str] = {
    "RA001": (
        "footgun jnp ufunc-method API (jnp.maximum.accumulate and friends): "
        "silently falls back to host numpy and breaks under tracing — use "
        "lax.cummax / lax.associative_scan"
    ),
    "RA002": (
        "donate_argnums/donate_argnames without a platform guard: donated "
        "buffers + the persistent compile cache corrupt the heap on XLA:CPU "
        "(jax 0.4.37) — gate donation on jax.default_backend()"
    ),
    "RA003": (
        "host sync inside a traced body (.item(), float(tracer), "
        "np.asarray(device_value)): forces a device round-trip per trace "
        "step or fails outright under jit"
    ),
    "RA004": (
        "dtype-literal drift in an x64-parity function: a hard-coded "
        "float32 inside a function threaded through the x64 ladder silently "
        "truncates the f64 parity path — derive the dtype from the ladder "
        "(e.g. jnp.float64 if x64 else jnp.float32)"
    ),
    "RA005": (
        "jax.experimental.enable_x64 is gone from the installed jax: use the "
        "jax.enable_x64 context, and only in CPU reference code — no device "
        "program of the main path runs in float64 (the TPU cannot compile "
        "its f64 ops)"
    ),
    "RA006": (
        "Python control flow on a tracer-valued test inside a traced body: "
        "raises ConcretizationTypeError or silently specializes on one "
        "branch — use lax.cond / jnp.where"
    ),
}

# Transforms whose function arguments become traced scopes.
_TRACING_CALLS = {
    "jax.jit",
    "jax.pjit",
    "jax.vmap",
    "jax.pmap",
    "jax.grad",
    "jax.value_and_grad",
    "jax.checkpoint",
    "jax.remat",
    "jax.lax.scan",
    "jax.lax.while_loop",
    "jax.lax.fori_loop",
    "jax.lax.cond",
    "jax.lax.switch",
    "jax.lax.map",
    "jax.lax.associative_scan",
    "jax.experimental.shard_map.shard_map",
}

# jnp attributes whose *method* use reproduces the RA001 bug class.
_UFUNC_METHODS = {"accumulate", "reduce", "reduceat", "outer"}

# RA003: method calls that force host sync on a device value.
_HOST_SYNC_METHODS = {"item", "tolist"}
# RA003: callables that materialize a host array from a traced value.
_HOST_MATERIALIZERS = {"numpy.asarray", "numpy.array", "numpy.copy"}

_X64_DTYPE_PARAMS = {"x64", "dtype"}
_F32_ATTRS = {"jax.numpy.float32", "numpy.float32"}


def _import_aliases(tree: ast.Module) -> dict[str, str]:
    """Map local names to canonical dotted prefixes for the modules we know."""
    known_roots = ("jax", "numpy", "functools")
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for al in node.names:
                if al.name.split(".")[0] in known_roots:
                    aliases[al.asname or al.name.split(".")[0]] = (
                        al.name if al.asname else al.name.split(".")[0]
                    )
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] in known_roots:
                for al in node.names:
                    aliases[al.asname or al.name] = f"{node.module}.{al.name}"
    return aliases


class _Checker:
    def __init__(self, tree: ast.Module, path: str, source_lines: list[str]):
        self.tree = tree
        self.path = path
        self.lines = source_lines
        self.aliases = _import_aliases(tree)
        self.findings: list[Finding] = []
        self.traced_names = self._collect_traced_names()
        self.traced_lambda_ids = self._traced_lambda_ids
        self.module_is_x64 = self._module_is_x64()
        # Walk state.
        self._traced_depth = 0
        self._func_stack: list[ast.AST] = []
        self._ra004_param: str | None = None  # active x64/dtype param name
        self._ra004_exempt = 0  # inside a ladder-selecting IfExp / defaults

    # ---- name resolution -------------------------------------------------

    def _dotted(self, node: ast.AST) -> str | None:
        """Canonical dotted path for a Name/Attribute chain, else None."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.aliases.get(node.id, node.id)
        parts.append(root)
        return ".".join(reversed(parts))

    # ---- traced-scope discovery -----------------------------------------

    def _harvest_traced_args(self, call: ast.Call, names: set[str], lambdas: set[int]):
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(arg, ast.Name):
                names.add(arg.id)
            elif isinstance(arg, ast.Lambda):
                lambdas.add(id(arg))
            elif isinstance(arg, ast.Call):
                fn = self._dotted(arg.func)
                if fn in _TRACING_CALLS or fn == "functools.partial":
                    self._harvest_traced_args(arg, names, lambdas)

    def _collect_traced_names(self) -> set[str]:
        names: set[str] = set()
        self._traced_lambda_ids: set[int] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and self._dotted(node.func) in _TRACING_CALLS:
                self._harvest_traced_args(node, names, self._traced_lambda_ids)
        return names

    def _decorator_traced(self, node: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            fn = self._dotted(target)
            if fn in _TRACING_CALLS:
                return True
            if fn == "functools.partial" and isinstance(dec, ast.Call):
                if dec.args and self._dotted(dec.args[0]) in _TRACING_CALLS:
                    return True
        return False

    def _module_is_x64(self) -> bool:
        """Does this module participate in the x64 parity ladder?"""
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ImportFrom):
                for al in node.names:
                    if al.name == "enable_x64":
                        return True
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                all_args = node.args.args + node.args.kwonlyargs
                if any(a.arg == "x64" for a in all_args):
                    return True
        return False

    # ---- findings --------------------------------------------------------

    def _emit(self, rule: str, node: ast.AST, message: str):
        line = getattr(node, "lineno", 1)
        src = self.lines[line - 1].strip() if 0 < line <= len(self.lines) else ""
        self.findings.append(
            Finding(rule, self.path, line, getattr(node, "col_offset", 0), message, src)
        )

    # ---- main walk -------------------------------------------------------

    def run(self) -> list[Finding]:
        for stmt in self.tree.body:
            self._visit(stmt)
        return self.findings

    def _visit(self, node: ast.AST):
        method = getattr(self, f"_visit_{type(node).__name__}", None)
        if method is not None:
            method(node)
        else:
            self._generic(node)

    def _generic(self, node: ast.AST):
        for child in ast.iter_child_nodes(node):
            self._visit(child)

    # -- imports (RA005) --

    def _visit_ImportFrom(self, node: ast.ImportFrom):
        if node.module == "jax.experimental":
            for al in node.names:
                if al.name == "enable_x64":
                    self._emit(
                        "RA005",
                        node,
                        "jax.experimental.enable_x64 import; use the jax.enable_x64 context",
                    )
        self._generic(node)

    # -- function scopes --

    def _visit_FunctionDef(self, node: ast.FunctionDef):
        self._enter_function(node)

    def _visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef):
        self._enter_function(node)

    def _enter_function(self, node):
        traced = (
            self._traced_depth > 0
            or node.name in self.traced_names
            or self._decorator_traced(node)
        )
        # Decorators and signature defaults evaluate at def time (host
        # context): visit them OUTSIDE the traced scope and exempt from
        # RA004 (a dtype=jnp.float32 default is the sanctioned spelling).
        for dec in node.decorator_list:
            self._visit(dec)
        self._ra004_exempt += 1
        for d in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            self._visit(d)
        self._ra004_exempt -= 1

        all_args = node.args.args + node.args.kwonlyargs + node.args.posonlyargs
        param = next((a.arg for a in all_args if a.arg in _X64_DTYPE_PARAMS), None)

        prev_param = self._ra004_param
        if param is not None and self.module_is_x64:
            self._ra004_param = param
        self._func_stack.append(node)
        self._traced_depth += traced
        for stmt in node.body:
            self._visit(stmt)
        self._traced_depth -= traced
        self._func_stack.pop()
        self._ra004_param = prev_param

    def _visit_Lambda(self, node: ast.Lambda):
        traced = self._traced_depth > 0 or id(node) in self.traced_lambda_ids
        self._ra004_exempt += 1
        for d in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            self._visit(d)
        self._ra004_exempt -= 1
        self._traced_depth += traced
        self._visit(node.body)
        self._traced_depth -= traced

    # -- expressions --

    def _visit_Attribute(self, node: ast.Attribute):
        # RA001: jnp.<ufunc>.<method>; the value chain must resolve to a
        # jax.numpy attribute (np.maximum.accumulate on host data is fine).
        if node.attr in _UFUNC_METHODS:
            base = self._dotted(node.value)
            if base is not None and base.startswith("jax.numpy."):
                self._emit(
                    "RA001",
                    node,
                    f"{base.replace('jax.numpy', 'jnp')}.{node.attr} is the host-"
                    "numpy ufunc method (the seed's segmentation bug); use "
                    "lax.cummax / lax.associative_scan",
                )
        full = self._dotted(node)
        if full == "jax.experimental.enable_x64":
            self._emit(
                "RA005", node, "jax.experimental.enable_x64 use; use the jax.enable_x64 context"
            )
        # RA004: hard-coded f32 inside an x64-laddered function body.
        if (
            self._ra004_param is not None
            and not self._ra004_exempt
            and full in _F32_ATTRS
        ):
            self._emit(
                "RA004",
                node,
                f"hard-coded {full.split('.')[-1]} inside x64-laddered function "
                f"(has `{self._ra004_param}` param); derive the dtype from the "
                "ladder",
            )
        self._generic(node)

    def _visit_Constant(self, node: ast.Constant):
        if (
            self._ra004_param is not None
            and not self._ra004_exempt
            and node.value == "float32"
        ):
            self._emit(
                "RA004",
                node,
                "hard-coded 'float32' string inside x64-laddered function; "
                "derive the dtype from the ladder",
            )

    def _visit_IfExp(self, node: ast.IfExp):
        # `jnp.float64 if x64 else jnp.float32` is THE sanctioned ladder
        # selection pattern: exempt both branches from RA004 when the test
        # references the ladder param (or the global x64 flag).
        exempt = self._ra004_param is not None and self._mentions_ladder(node.test)
        self._check_ra006_test(node)
        self._visit(node.test)
        self._ra004_exempt += exempt
        self._visit(node.body)
        self._visit(node.orelse)
        self._ra004_exempt -= exempt

    def _mentions_ladder(self, test: ast.AST) -> bool:
        for sub in ast.walk(test):
            if isinstance(sub, ast.Name) and sub.id in _X64_DTYPE_PARAMS:
                return True
            if isinstance(sub, ast.Attribute) and sub.attr in (
                "jax_enable_x64",
                "x64_enabled",
            ):
                return True
        return False

    def _visit_Call(self, node: ast.Call):
        # RA002: donation without a platform guard.
        for kw in node.keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                if not self._donation_guarded(node):
                    self._emit(
                        "RA002",
                        node,
                        f"{kw.arg} without a platform guard (donated buffers + "
                        "persistent compile cache corrupt the heap on XLA:CPU); "
                        "gate on jax.default_backend()",
                    )
                break
        if self._traced_depth > 0:
            self._check_ra003(node)
        self._generic(node)

    def _donation_guarded(self, node: ast.Call) -> bool:
        """True if the enclosing function (or module statement) consults the
        backend/platform before donating."""
        scope: ast.AST | None = self._func_stack[-1] if self._func_stack else None
        if scope is None:
            scope = self.tree
        for sub in ast.walk(scope):
            if isinstance(sub, ast.Attribute) and (
                sub.attr == "default_backend" or "platform" in sub.attr
            ):
                return True
            if isinstance(sub, ast.Name) and (
                sub.id == "default_backend" or "platform" in sub.id
            ):
                return True
        return False

    def _check_ra003(self, node: ast.Call):
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in _HOST_SYNC_METHODS:
            self._emit(
                "RA003",
                node,
                f".{func.attr}() inside a traced body forces a host sync "
                "(fails under jit); keep the value on device",
            )
            return
        dotted = self._dotted(func)
        if dotted in _HOST_MATERIALIZERS:
            self._emit(
                "RA003",
                node,
                f"{dotted.replace('numpy', 'np')}() on a traced value pulls it "
                "to host; use jnp inside traced code",
            )
            return
        if (
            isinstance(func, ast.Name)
            and func.id in ("float", "int", "bool")
            and func.id not in self.aliases
            and len(node.args) == 1
            and not isinstance(node.args[0], ast.Constant)
        ):
            self._emit(
                "RA003",
                node,
                f"builtin {func.id}() on a traced value concretizes it; use "
                "astype / jnp casts",
            )

    # -- statements --

    def _visit_If(self, node: ast.If):
        self._check_ra006_test(node)
        self._generic(node)

    def _visit_While(self, node: ast.While):
        self._check_ra006_test(node)
        self._generic(node)

    def _check_ra006_test(self, node):
        if self._traced_depth <= 0:
            return
        test = node.test
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call):
                dotted = self._dotted(sub.func)
                if dotted and (
                    dotted.startswith("jax.numpy.") or dotted.startswith("jax.lax.")
                ):
                    self._emit(
                        "RA006",
                        node,
                        "Python control flow on a tracer-valued test "
                        f"({dotted.replace('jax.numpy', 'jnp')}(...)); use "
                        "lax.cond / jnp.where",
                    )
                    return
                if (
                    isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in ("any", "all")
                    and self._dotted(sub.func.value) is None
                ):
                    self._emit(
                        "RA006",
                        node,
                        f"Python control flow on .{sub.func.attr}() of a traced "
                        "value; use lax.cond / jnp.where",
                    )
                    return


def check_source(source: str, path: str = "<string>") -> list[Finding]:
    """Run every rule over one module's source; returns raw findings
    (suppressions and baselines are applied by the engine layer)."""
    tree = ast.parse(source, filename=path)
    return _Checker(tree, path, source.splitlines()).run()
