"""Layer 1 — AST lint rules (``RPR0xx``).

Stdlib-``ast`` checks over ``src/repro`` enforcing the host/device seam
contracts that the jaxpr and Pallas layers cannot see (they only look at
what traces; these rules look at what is *written*):

  RPR001  host-sync primitive inside a jitted/traced function body
  RPR002  host-sync seam (device_get / .item() / block_until_ready /
          obs.fetch) in library code without an ALLOWLIST entry naming
          the seam
  RPR003  ``time.perf_counter`` outside ``src/repro/obs`` — spans/clocks
          are the one timing seam
  RPR004  kernel entry point whose ``interpret`` default is not ``None``
          (``kernels/backend.resolve_interpret`` is the only resolver)
  RPR005  non-literal / non-allowlisted ``static_argnames`` at a
          ``jax.jit`` build site; implicit-``maxsize`` ``lru_cache``

"Traced" is decided statically: a function is traced when it is decorated
with ``jax.jit`` (directly or through ``functools.partial``), passed as an
operand to a tracing combinator (``jit``/``vmap``/``pmap``/``shard_map``/
``lax.fori_loop``/``while_loop``/``cond``/``scan``/``switch``/
``pallas_call`` — including through ``functools.partial``), or defined
inside such a function.

The seam ALLOWLIST below is the machine-readable registry of every place
the architecture *intends* a host sync: level-plan barriers (the next
level's shapes depend on the device's max degree), end-of-run result
materialisation, checkpoint device→host transfer, elastic re-meshing, and
the obs layer's ``sp.sync()`` and ``obs.fetch`` (the counted read the
single-device ``pc`` path makes all its blocking reads through; a call of
it is a sync primitive like ``device_get``). Findings at those keys never surface; a new
sync anywhere else fails CI until it is either removed or added here with
a justification.
"""
from __future__ import annotations

import ast
from pathlib import Path, PurePosixPath

from .findings import Finding, register_rule

RPR001 = register_rule(
    "RPR001", "host-sync primitive inside a jitted/traced function body"
)
RPR002 = register_rule(
    "RPR002", "host-sync seam in library code without an allowlist entry"
)
RPR003 = register_rule(
    "RPR003", "time.perf_counter outside src/repro/obs (spans are the timing seam)"
)
RPR004 = register_rule(
    "RPR004", "kernel entry point must default interpret=None (backend resolves)"
)
RPR005 = register_rule(
    "RPR005", "non-literal/non-allowlisted static_argnames or implicit lru_cache"
)

#: Call targets that trace their function operands.
_TRACING_TAILS = {
    "jit", "vmap", "pmap", "fori_loop", "while_loop", "cond", "scan",
    "switch", "shard_map", "pallas_call", "checkpoint", "remat", "custom_jvp",
    "custom_vjp", "grad", "value_and_grad",
}

#: static_argnames every jit build site may use — the planner/kernel static
#: shape vocabulary. A new static name is a new compile-cache axis; adding
#: it here is the explicit opt-in.
STATIC_ARGNAME_ALLOWLIST = {
    "ell", "n_chunk", "n_max", "r", "q", "use_kernel", "bm", "bi", "bj",
    "bk", "bn", "bs", "bp", "npr", "tb", "jitter", "interpret",
    "vote_chunk", "depth", "n_prime",
}

#: Seam registry: Finding.key -> one-line justification. Keys are
#: line-independent (``CODE path::function::primitive``), so refactors that
#: move a seam within its function do not churn this table.
ALLOWLIST: dict[str, str] = {
    # ---- level-plan barriers: the next level's static shapes (n', chunking)
    # ---- depend on the device-side max degree; one sync per level by design
    "RPR002 src/repro/core/levels.py::run_level::np.asarray(obs.fetch)":
        "per-level plan barrier: chunk shapes derive from the device max degree",
    "RPR002 src/repro/core/levels.py::run_level_classes::np.asarray(obs.fetch)":
        "per-level plan barrier: the degree classes derive from the row counts",
    "RPR002 src/repro/core/pc.py::_pc_run_host_loop::obs.fetch":
        "level-ladder barrier: max_deg decides whether another level runs",
    "RPR002 src/repro/core/distributed.py::run_level_sharded::np.asarray(device_get)":
        "sharded per-level plan barrier (same contract as levels.run_level)",
    "RPR002 src/repro/core/distributed.py::pc_distributed::device_get":
        "distributed level-ladder barrier on the gathered max degree",
    "RPR002 src/repro/core/engines.py::_run_level_dense_l1::obs.fetch":
        "dense-l1 planner reads the max degree to size the compacted commit",
    "RPR002 src/repro/batch/scan_pc.py::plan_n_prime::obs.fetch":
        "scan planner: one sync for the exact level-0 degree bound (documented)",
    "RPR002 src/repro/batch/scan_pc.py::_prep::obs.fetch":
        "discrete scan planner: level-0 degree bound before the traced build",
    "RPR002 src/repro/batch/scan_pc.py::scan_levels_batch::device_get":
        "batch schedule barrier: the shared width is the batch max degree",
    # ---- end-of-run result materialisation: PCRun/ScanResult fields are
    # ---- numpy by contract (the public API boundary)
    "RPR002 src/repro/core/pc.py::_pc_run_host_loop::np.asarray(obs.fetch)":
        "PCRun materialisation: public result fields are host numpy by contract",
    "RPR002 src/repro/core/pc.py::_pc_run_scan::np.asarray(obs.fetch)":
        "PCRun materialisation of the traced-scan outputs (API boundary)",
    "RPR002 src/repro/core/distributed.py::pc_distributed::np.asarray(device_get)":
        "PCRun materialisation after the distributed run (API boundary)",
    "RPR002 src/repro/batch/ensemble.py::bootstrap_pc::np.asarray(device_get)":
        "EnsembleRun materialisation: aggregate outputs are host numpy",
    # ---- infrastructure seams
    "RPR002 src/repro/checkpoint/manager.py::save_tree::np.asarray(device_get)":
        "checkpointing IS the device->host transfer (sync save path)",
    "RPR002 src/repro/checkpoint/manager.py::save::np.asarray(device_get)":
        "checkpointing IS the device->host transfer (async save path)",
    "RPR002 src/repro/distributed/elastic.py::remesh::device_get":
        "elastic re-meshing round-trips through host to re-place shards",
    "RPR002 src/repro/obs/trace.py::_block::block_until_ready":
        "sp.sync(): the sanctioned span sync so span timings measure device work",
    "RPR002 src/repro/obs/trace.py::fetch::device_get":
        "obs.fetch(): the counted seam every blocking read of the pc path uses",
    "RPR002 src/repro/core/cit.py::threshold::obs.fetch":
        "tau's inverse normal runs as jax ndtri on the device; its scalar comes back",
    "RPR002 src/repro/core/validate.py::_as_host::np.asarray(obs.fetch)":
        "admission reads device-resident samples back to check them on host",
}


def _dotted(node) -> str | None:
    """'jax.lax.fori_loop' for nested Attribute/Name chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _tail(node) -> str | None:
    d = _dotted(node)
    return d.rsplit(".", 1)[-1] if d else None


def _is_partial(call: ast.Call) -> bool:
    return isinstance(call, ast.Call) and _tail(call.func) == "partial"


def _jit_decorated(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
    for dec in fn.decorator_list:
        if _tail(dec) in ("jit", "pjit"):
            return True
        if isinstance(dec, ast.Call):
            if _tail(dec.func) in ("jit", "pjit"):
                return True
            if _is_partial(dec) and dec.args and _tail(dec.args[0]) in ("jit", "pjit"):
                return True
    return False


def _traced_operand_names(tree: ast.AST) -> set[str]:
    """Names of functions passed (possibly via functools.partial) to a
    tracing combinator anywhere in the module."""
    names: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or _tail(node.func) not in _TRACING_TAILS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name):
                names.add(arg.id)
            elif isinstance(arg, ast.Call) and _is_partial(arg) and arg.args:
                inner = _tail(arg.args[0])
                if inner:
                    names.add(inner)
    return names


_FN = (ast.FunctionDef, ast.AsyncFunctionDef)


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, allowlist: dict[str, str]):
        self.path = path
        self.allow = allowlist
        self.findings: list[Finding] = []
        self.stack: list[str] = []  # enclosing function names
        self.traced_depth = 0  # >0 while inside a traced function
        self.traced_names: set[str] = set()
        p = PurePosixPath(path)
        self.in_obs = "obs" in p.parts
        self.in_kernels = "kernels" in p.parts
        self.in_launch = "launch" in p.parts
        self.is_backend = p.name == "backend.py" and self.in_kernels

    # ---------------------------------------------------------------- emit
    def _emit(self, code, node, message, detail):
        f = Finding(
            code=code, path=self.path, line=getattr(node, "lineno", 0),
            message=message, context=self.stack[-1] if self.stack else "<module>",
            detail=detail,
        )
        if f.key not in self.allow:
            self.findings.append(f)

    # ------------------------------------------------------------ functions
    def visit_FunctionDef(self, node):
        self._function(node)

    def visit_AsyncFunctionDef(self, node):
        self._function(node)

    def _function(self, node):
        traced = (
            self.traced_depth > 0
            or _jit_decorated(node)
            or node.name in self.traced_names
        )
        if self.in_kernels:
            self._check_interpret_default(node)
        self._check_decorator_sites(node)
        self.stack.append(node.name)
        if traced:
            self.traced_depth += 1
        self.generic_visit(node)
        if traced:
            self.traced_depth -= 1
        self.stack.pop()

    def _check_interpret_default(self, node):
        args = node.args
        named = list(args.args) + list(args.kwonlyargs)
        defaults = dict(
            zip([a.arg for a in args.args[len(args.args) - len(args.defaults):]],
                args.defaults)
        )
        defaults.update(
            {a.arg: d for a, d in zip(args.kwonlyargs, args.kw_defaults)
             if d is not None}
        )
        for a in named:
            if a.arg != "interpret":
                continue
            d = defaults.get(a.arg)
            ok = isinstance(d, ast.Constant) and d.value is None
            if not ok:
                self._emit(
                    RPR004, node,
                    f"kernel entry `{node.name}` must default interpret=None "
                    "(kernels/backend.resolve_interpret is the only resolver)",
                    "interpret-default",
                )
        if node.name == "resolve_interpret" and not self.is_backend:
            self._emit(
                RPR004, node,
                "resolve_interpret may only be defined in kernels/backend.py",
                "resolver-definition",
            )

    def _check_decorator_sites(self, node):
        for dec in node.decorator_list:
            if _tail(dec) == "lru_cache" and not isinstance(dec, ast.Call):
                self._emit(
                    RPR005, dec,
                    f"`{node.name}`: bare @lru_cache caches 128 entries "
                    "implicitly — declare maxsize explicitly",
                    "lru_cache-maxsize",
                )

    # ---------------------------------------------------------------- calls
    def visit_Call(self, node):
        tail = _tail(node.func)
        dotted = _dotted(node.func) or ""

        # RPR005: jit build sites + lru_cache calls
        jit_call = tail in ("jit", "pjit") or (
            _is_partial(node) and node.args and _tail(node.args[0]) in ("jit", "pjit")
        )
        if jit_call:
            self._check_static_argnames(node)
        if tail == "lru_cache" and not node.args and not any(
            kw.arg == "maxsize" for kw in node.keywords
        ):
            self._emit(
                RPR005, node,
                "lru_cache() without an explicit maxsize caches 128 entries "
                "implicitly — declare maxsize (None for unbounded is explicit)",
                "lru_cache-maxsize",
            )

        # RPR004: hardcoded interpret at a pallas_call site
        if tail == "pallas_call":
            for kw in node.keywords:
                if kw.arg == "interpret" and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, bool):
                    self._emit(
                        RPR004, kw.value,
                        "pallas_call with hardcoded interpret= constant — "
                        "thread the resolved flag through the entry point",
                        "interpret-hardcoded",
                    )

        # host-sync primitives
        sync = None
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item" \
                and not node.args and not node.keywords:
            sync = ".item()"
        elif tail == "device_get":
            sync = "device_get"
        elif dotted == "obs.fetch" or dotted.endswith(".obs.fetch"):
            sync = "obs.fetch"
        elif tail == "block_until_ready" or (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "block_until_ready"
        ):
            sync = "block_until_ready"

        if self.traced_depth > 0:
            traced_sync = sync
            if dotted in ("np.asarray", "numpy.asarray", "np.array", "numpy.array"):
                traced_sync = "np.asarray"
            elif isinstance(node.func, ast.Name) and node.func.id == "float" \
                    and node.args and not isinstance(node.args[0], ast.Constant):
                traced_sync = "float()"
            if traced_sync:
                self._emit(
                    RPR001, node,
                    f"`{traced_sync}` inside a traced function forces a host "
                    "sync at trace/dispatch time — hoist it out of the jitted "
                    "body",
                    traced_sync,
                )
        elif sync and not self.in_launch:
            detail = sync
            # collapse the idiomatic np.asarray(jax.device_get(x)) pair into
            # one seam key so the allowlist names the materialisation once
            if sync in ("device_get", "obs.fetch") and self._inside_np_asarray(node):
                detail = f"np.asarray({sync})"
            self._emit(
                RPR002, node,
                f"host sync `{sync}` in library code — every seam must be "
                "named in analysis.rules.ALLOWLIST with a justification",
                detail,
            )

        # RPR003: perf_counter outside obs/
        if tail == "perf_counter" and not self.in_obs:
            self._emit(
                RPR003, node,
                "time.perf_counter outside src/repro/obs — use the obs "
                "clocks/spans (the one timing seam) so tests can inject time",
                "perf_counter",
            )
        self.generic_visit(node)

    def _inside_np_asarray(self, node) -> bool:
        parent = getattr(node, "_parent_call", None)
        return parent is not None

    def _check_static_argnames(self, node):
        for kw in node.keywords:
            if kw.arg != "static_argnames":
                continue
            v = kw.value
            names = None
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names = [v.value]
            elif isinstance(v, (ast.Tuple, ast.List)) and all(
                isinstance(e, ast.Constant) and isinstance(e.value, str)
                for e in v.elts
            ):
                names = [e.value for e in v.elts]
            if names is None:
                self._emit(
                    RPR005, v,
                    "static_argnames must be a literal str/tuple of strs — "
                    "computed values defeat the compile-cache audit",
                    "static_argnames-nonliteral",
                )
                continue
            for n in names:
                if n not in STATIC_ARGNAME_ALLOWLIST:
                    self._emit(
                        RPR005, v,
                        f"static argname `{n}` is not in the planner/kernel "
                        "static vocabulary (STATIC_ARGNAME_ALLOWLIST) — new "
                        "compile-cache axes are an explicit opt-in",
                        f"static_argnames:{n}",
                    )


def _annotate_asarray_parents(tree):
    """Mark device_get / obs.fetch calls that sit directly inside
    np.asarray(...) so the pair collapses to one 'np.asarray(<sync>)' seam
    key."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and (
            _dotted(node.func) in ("np.asarray", "numpy.asarray")
        ):
            for arg in node.args:
                if isinstance(arg, ast.Call) and _tail(arg.func) in ("device_get", "fetch"):
                    arg._parent_call = node


def check_source(
    src: str, path: str, allowlist: dict[str, str] | None = None
) -> list[Finding]:
    """Run every Layer-1 rule over one module's source text. ``path`` is the
    repo-relative posix path and decides scope (obs/kernels/launch)."""
    tree = ast.parse(src)
    _annotate_asarray_parents(tree)
    v = _Visitor(path, ALLOWLIST if allowlist is None else allowlist)
    v.traced_names = _traced_operand_names(tree)
    v.visit(tree)
    # bare `from time import perf_counter` aliasing counts as a use
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name == "perf_counter" and not v.in_obs:
                    v.findings.append(Finding(
                        code=RPR003, path=path, line=node.lineno,
                        message="importing perf_counter outside src/repro/obs "
                                "— use the obs clocks/spans",
                        context="<module>", detail="perf_counter-import",
                    ))
    return v.findings


def check_file(
    file: Path, repo_root: Path, allowlist: dict[str, str] | None = None
) -> list[Finding]:
    rel = file.resolve().relative_to(repo_root.resolve()).as_posix()
    return check_source(file.read_text(), rel, allowlist)


def check_tree(
    repo_root: Path, subdir: str = "src/repro",
    allowlist: dict[str, str] | None = None,
) -> list[Finding]:
    """Sweep every .py under ``repo_root/subdir``."""
    root = Path(repo_root)
    out: list[Finding] = []
    for f in sorted((root / subdir).rglob("*.py")):
        out.extend(check_file(f, root, allowlist))
    return out
