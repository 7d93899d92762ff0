"""dtype-discipline: float64 op-order contract in the compiled engine.

Event times in the jax DES tier must be IEEE-754 identical to the host
engines, which means every constant entering time arithmetic is float64
and roofline constants flow through ``timing.constants_f64()`` (or an
explicit ``float``/``np.float64`` wrap).  Within the manifest's
f64-critical files this rule flags:

* references to reduced-precision dtypes (``float32``/``float16``/
  ``bfloat16``) outside manifest-allowed scopes — the allowed scopes are
  the documented jax-tier divergences (the float32 AIMD controller
  mirror), recorded with reasons in the tolerance manifest;
* jnp array constructors whose fill value is a bare float literal with
  no explicit dtype (``jnp.asarray(1e-9)``) — weak-typed constants
  silently degrade to float32 when x64 is not enabled;
* ``jnp.zeros/ones/empty/full`` with no dtype argument at all;
* unwrapped reads of the roofline constants (``.w_base``/``.h_per_seq``)
  — they must pass through ``float()``/``np.float64()`` or
  ``timing.constants_f64()``;
* calls to the manifest's x64 entry points (``_runner``) outside a
  ``with enable_x64():`` block.

Device kernels (``repro/kernels/*``) are deliberately outside this
rule's file set — see the manifest's ``kernels_note``.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from repro.analysis.core import (
    Finding,
    Rule,
    SourceFile,
    enclosing_map,
    register,
    scope_chain,
    unparse,
)

_LOW_PRECISION = {"float32", "float16", "bfloat16"}
_CTORS_DTYPE_POS = {  # constructor -> index of the positional dtype arg
    "zeros": 1,
    "ones": 1,
    "empty": 1,
    "full": 2,
    "array": 1,
    "asarray": 1,
}
_FILL_POS = {"full": 1, "array": 0, "asarray": 0}


def _is_float_literal(node: ast.expr) -> bool:
    if isinstance(node, ast.Constant) and type(node.value) is float:
        return True
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, (ast.USub, ast.UAdd)):
        return _is_float_literal(node.operand)
    return False


@register
class DtypeDisciplineRule(Rule):
    name = "dtype-discipline"
    description = (
        "f64-critical files: no float32-family constants or implicit-"
        "dtype jnp constructors; roofline constants wrapped in f64; "
        "jit entries under enable_x64()"
    )

    def check(self, sf: SourceFile) -> Iterable[Finding]:
        cfg = self.manifest.get("dtype", {})
        if not any(sf.matches(p) for p in cfg.get("files", [])):
            return ()
        findings: List[Finding] = []
        enclosing = enclosing_map(sf.tree)
        allowed_scopes: set = set()
        for path, scopes in cfg.get("float32_scope_ok", {}).items():
            if sf.matches(path):
                allowed_scopes |= set(scopes)
        const_attrs = set(cfg.get("const_attrs", []))
        wrappers = set(cfg.get("const_wrappers", ["float", "np.float64"]))
        x64_entries: set = set()
        for path, names in cfg.get("x64_entries", {}).items():
            if sf.matches(path):
                x64_entries |= set(names)

        # parent map for the const-wrap and x64 checks
        parents = {}
        for node in ast.walk(sf.tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node

        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Attribute) and node.attr in _LOW_PRECISION:
                if not (set(scope_chain(node, enclosing)) & allowed_scopes):
                    findings.append(
                        Finding(
                            rule=self.name,
                            path=sf.ident,
                            line=node.lineno,
                            message=(
                                f"reduced-precision dtype `{unparse(node)}` in "
                                f"an f64-critical file"
                            ),
                            hint=(
                                "event-time math must stay float64 "
                                "(timing.constants_f64()); if this scope is an "
                                "intentional jax-tier divergence, record it "
                                "under dtype.float32_scope_ok in the tolerance "
                                "manifest with a reason"
                            ),
                        )
                    )
            elif isinstance(node, ast.Call):
                findings.extend(self._check_ctor(node, sf))
                findings.extend(self._check_x64(node, sf, x64_entries, parents))
            elif isinstance(node, ast.keyword) and node.arg == "dtype":
                v = node.value
                if isinstance(v, ast.Constant) and v.value in _LOW_PRECISION:
                    if not (set(scope_chain(node.value, enclosing)) & allowed_scopes):
                        findings.append(
                            Finding(
                                rule=self.name,
                                path=sf.ident,
                                line=v.lineno,
                                message=(
                                    f'reduced-precision dtype string '
                                    f'"{v.value}" in an f64-critical file'
                                ),
                                hint="use an explicit x64 dtype",
                            )
                        )
            elif isinstance(node, ast.Attribute) and node.attr in const_attrs:
                par = parents.get(node)
                wrapped = (
                    isinstance(par, ast.Call)
                    and node in par.args
                    and unparse(par.func) in wrappers
                )
                if not wrapped:
                    findings.append(
                        Finding(
                            rule=self.name,
                            path=sf.ident,
                            line=node.lineno,
                            message=(
                                f"roofline constant `{unparse(node)}` used "
                                f"without an explicit f64 wrap"
                            ),
                            hint=(
                                "read it via timing.constants_f64() or wrap "
                                "in float()/np.float64() so device and host "
                                "accumulate identical event times"
                            ),
                        )
                    )
        return findings

    def _check_ctor(self, call: ast.Call, sf: SourceFile) -> Iterable[Finding]:
        if not isinstance(call.func, ast.Attribute):
            return ()
        if not (
            isinstance(call.func.value, ast.Name) and call.func.value.id == "jnp"
        ):
            return ()
        name = call.func.attr
        if name not in _CTORS_DTYPE_POS:
            return ()
        has_dtype = any(k.arg == "dtype" for k in call.keywords) or len(
            call.args
        ) > _CTORS_DTYPE_POS[name]
        if has_dtype:
            return ()
        fill_idx = _FILL_POS.get(name)
        fill_is_float = (
            fill_idx is not None
            and fill_idx < len(call.args)
            and _is_float_literal(call.args[fill_idx])
        )
        if name in ("array", "asarray") and not fill_is_float:
            return ()  # int/bool literals and array args keep their dtype
        if name == "full" and not fill_is_float:
            # non-literal fill inherits its operand dtype; still covered
            # by the zeros/ones/empty explicitness rule below only when
            # the fill is a literal, so let it pass here.
            return ()
        what = (
            f"bare float literal in `jnp.{name}(...)`"
            if fill_is_float
            else f"`jnp.{name}(...)` without an explicit dtype"
        )
        return (
            Finding(
                rule=self.name,
                path=sf.ident,
                line=call.lineno,
                message=f"{what} — weak-typed constant may degrade to float32",
                hint="pass an explicit x64 dtype (e.g. jnp.float64/i32)",
            ),
        )

    def _check_x64(
        self, call: ast.Call, sf: SourceFile, entries: set, parents: dict
    ) -> Iterable[Finding]:
        fn = call.func
        name = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None
        )
        if name not in entries:
            return ()
        node: ast.AST = call
        while node in parents:
            node = parents[node]
            if isinstance(node, (ast.With, ast.AsyncWith)) and any(
                "enable_x64" in unparse(item.context_expr) for item in node.items
            ):
                return ()
        return (
            Finding(
                rule=self.name,
                path=sf.ident,
                line=call.lineno,
                message=(
                    f"jit entry `{name}(...)` called outside a "
                    f"`with enable_x64():` block"
                ),
                hint=(
                    "event times are float64 accumulations; run compiled "
                    "entries under jax.enable_x64()"
                ),
            ),
        )
