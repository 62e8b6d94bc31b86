"""Head 1 — the template verifier: an AST pass pipeline over uploaded
model source. Zero untrusted code runs here; everything is syntax.

The reference validated uploads by dynamically loading the class
(reference model/model.py:244-273) — which executes module top-level
code and only proves the class *imports*. These passes prove the things
that otherwise burn a trial (or a chip-hour) to discover:

- structural contract: the six required BaseModel methods exist,
  ``get_knob_config`` is a real @staticmethod whose return value is
  *literally evaluable* (the advisor needs the space without running
  user code), declared ``dependencies`` cover every non-platform import;
- PopulationSpec consistency for the vmapped trial path (PR-8):
  ``dynamic_knobs`` ⊆ knob config, all three ``*_population`` methods
  overridden, and no Python branching on a dynamic knob inside the
  train path (members of one program must share one trace);
- JAX tracing pitfalls inside jit/vmap-reachable code: host syncs
  (``.item()``/``float()``/``np.asarray``), mutation of ``self`` under
  trace, and the legacy global ``numpy.random`` API;
- sandbox policy: imports the jail would refuse anyway fail at upload.

The report's ``capabilities`` dict is the single static capability
oracle — :func:`static_population_capability` replaces doctor.py's old
``b"population_spec" in bytes`` source sniff.
"""

from __future__ import annotations

import ast
from typing import Any, Dict, List, Optional, Set, Tuple

from rafiki_tpu.analysis import astutil
from rafiki_tpu.analysis.findings import ERROR, WARN, VerificationReport

REQUIRED_METHODS = ("get_knob_config", "train", "evaluate", "predict",
                    "dump_parameters", "load_parameters")
POPULATION_METHODS = ("train_population", "evaluate_population",
                      "dump_member_parameters")

#: knob constructors the advisor ships (sdk/knob.py); anything else
#: named ``*Knob`` is accepted too so templates can subclass BaseKnob
KNOWN_KNOB_CLASSES = {"IntegerKnob", "FloatKnob", "CategoricalKnob",
                      "FixedKnob"}

#: modules every worker environment provides without declaration: the
#: stdlib, the platform package itself, and the baked jax_graft
#: toolchain (mirrors sdk/deps.py's notion of "already importable")
IMPLICIT_MODULES = astutil.STDLIB_MODULES | {
    "rafiki_tpu", "numpy", "jax", "jaxlib", "optax"}

#: imports the sandbox (sdk/sandbox.py) exists to contain — a template
#: that needs these is hostile or misdesigned, and upload is the
#: cheapest place to say so. ``socket`` stays allowed: the default
#: jail shares the host netns (trials may need sockets) and
#: tests/test_sandbox.py documents that boundary.
FORBIDDEN_IMPORTS = {"subprocess", "ctypes", "pty", "resource", "pwd",
                     "grp", "setuptools", "pip", "ensurepip"}

#: pip-name -> import-name exceptions for the dependency check
_DIST_TO_IMPORT = {"scikit-learn": "sklearn", "pillow": "PIL",
                   "opencv-python": "cv2", "pyyaml": "yaml",
                   "beautifulsoup4": "bs4"}

#: legacy global-state numpy.random functions (np.random.seed & friends)
#: — process-wide RNG state breaks reproducibility under vmapped
#: populations and forked sandbox children; np.random.default_rng /
#: Generator thread state explicitly and stay allowed
_LEGACY_NP_RANDOM = {
    "seed", "rand", "randn", "randint", "random", "random_sample",
    "ranf", "sample", "uniform", "normal", "standard_normal", "choice",
    "permutation", "shuffle", "beta", "binomial", "poisson",
    "exponential", "gamma", "laplace", "lognormal", "multinomial"}

#: call names that trace their function argument(s)
_TRACING_CALLS = {"jit", "vmap", "pmap", "scan", "while_loop", "cond",
                  "fori_loop", "checkpoint", "remat"}

#: host-sync coercions that force a traced value to the host
_HOST_SYNC_NAMES = {"float", "int", "bool"}


def verify_template_source(
        source: str,
        class_name: Optional[str] = None,
        declared_dependencies: Optional[Dict[str, Optional[str]]] = None,
        filename: str = "<uploaded>",
) -> VerificationReport:
    """Run the full pass pipeline; never raises on bad input — every
    problem becomes a finding so callers get ONE shape to handle."""
    report = VerificationReport(class_name=class_name)
    try:
        tree = astutil.parse(source, filename)
    except SyntaxError as e:
        report.add("TPL005", f"template does not parse: {e.msg}",
                   ERROR, filename, int(e.lineno or 0), int(e.offset or 0))
        return report

    classes = astutil.class_map(tree)
    target = _resolve_target_class(report, classes, class_name, filename)
    _check_imports(report, tree, classes, target, declared_dependencies,
                   filename)
    if target is None:
        return report

    methods = astutil.own_and_inherited_methods(target, classes)
    knob_names = _check_structure(report, tree, target, classes, methods,
                                  filename)
    spec = _check_population(report, target, classes, methods, knob_names,
                             filename)
    gen_spec = _check_generation(report, target, classes, methods, filename)
    _check_jax_pitfalls(report, tree, filename)
    report.capabilities = {
        "population": spec is not None,
        "population_spec": spec,
        "generation": gen_spec is not None,
        "generation_spec": gen_spec,
    }
    return report


def verify_template_bytes(
        model_file_bytes: bytes,
        class_name: Optional[str] = None,
        declared_dependencies: Optional[Dict[str, Optional[str]]] = None,
        filename: str = "<uploaded>",
) -> VerificationReport:
    """Byte-level entry point for the upload path (Admin.create_model)."""
    try:
        source = model_file_bytes.decode("utf-8")
    except UnicodeDecodeError as e:
        report = VerificationReport(class_name=class_name)
        report.add("TPL005", f"template is not UTF-8 text: {e}", ERROR,
                   filename)
        return report
    return verify_template_source(source, class_name,
                                  declared_dependencies, filename)


def static_population_capability(
        source, class_name: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The static mirror of sdk/model.population_capability: the parsed
    PopulationSpec dict iff the template declares one AND overrides all
    three population methods — else None. THE capability oracle for
    callers that must not execute uploaded code (doctor.py); replaces
    the old ``b"population_spec" in bytes`` sniff."""
    if isinstance(source, bytes):
        report = verify_template_bytes(source, class_name)
    else:
        report = verify_template_source(source, class_name)
    if report.capabilities.get("population"):
        return report.capabilities.get("population_spec")
    return None


def static_generation_capability(
        source, class_name: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The static mirror of sdk/model.generation_capability: the parsed
    GenerationSpec dict iff the template declares one AND overrides the
    three decode methods — else None. THE capability oracle for callers
    that must not execute uploaded code (Admin.create_train_job's
    task/capability consistency check, doctor.py)."""
    if isinstance(source, bytes):
        report = verify_template_bytes(source, class_name)
    else:
        report = verify_template_source(source, class_name)
    if report.capabilities.get("generation"):
        return report.capabilities.get("generation_spec")
    return None


# -- pass: class resolution -------------------------------------------------

def _resolve_target_class(
        report: VerificationReport, classes: Dict[str, ast.ClassDef],
        class_name: Optional[str], filename: str,
) -> Optional[ast.ClassDef]:
    if class_name is not None:
        cls = classes.get(class_name)
        if cls is None:
            report.add("TPL004",
                       f"class {class_name!r} not found in template", ERROR,
                       filename)
            return None
        if not astutil.is_model_subclass(cls, classes):
            report.add("TPL004",
                       f"class {class_name!r} does not subclass BaseModel",
                       ERROR, filename, cls.lineno)
            return None
        return cls
    candidates = [c for c in classes.values()
                  if astutil.is_model_subclass(c, classes)]
    if not candidates:
        report.add("TPL004", "no BaseModel subclass found in template",
                   ERROR, filename)
        return None
    # last definition wins, matching what an import-and-getattr would see
    cls = candidates[-1]
    report.class_name = cls.name
    return cls


# -- pass: imports vs declared dependencies + sandbox policy ----------------

def _check_imports(
        report: VerificationReport, tree: ast.Module,
        classes: Dict[str, ast.ClassDef], target: Optional[ast.ClassDef],
        declared_dependencies: Optional[Dict[str, Optional[str]]],
        filename: str) -> None:
    imports = astutil.imported_top_modules(tree)
    # the sandbox-policy pass sees EVERY import, even ones a hostile
    # template hides behind try/except or a __main__ guard
    all_imports = astutil.imported_top_modules(tree, include_guarded=True)
    declared: Set[str] = set()
    deps = declared_dependencies
    if deps is None and target is not None:
        node = astutil.class_attr_assign(target, classes, "dependencies")
        if node is not None:
            if astutil.is_constant(node):
                try:
                    deps = astutil.literal_value(node)
                except ValueError:
                    # unevaluable corner (unhashable key, div-zero):
                    # same contract as a non-literal dict
                    deps = None
                if deps is not None and not isinstance(deps, dict):
                    report.add("TPL007",
                               "dependencies attribute must be a dict of "
                               f"{{package: version}}, got "
                               f"{type(deps).__name__}", WARN, filename,
                               node.lineno)
                    deps = None
            else:
                report.add("TPL007",
                           "dependencies attribute is not a literal dict — "
                           "the platform cannot provision what it cannot "
                           "read statically", WARN, filename, node.lineno)
    for name in (deps or {}):
        lowered = str(name).lower()
        declared.add(_DIST_TO_IMPORT.get(lowered, lowered.replace("-", "_")))
        declared.add(str(name))
    for mod, lineno in sorted(all_imports.items(), key=lambda kv: kv[1]):
        if mod in FORBIDDEN_IMPORTS:
            report.add("SBX001",
                       f"import of {mod!r} is forbidden in the trial "
                       "sandbox — a template must not spawn processes or "
                       "load native code (docs/static-analysis.md)", ERROR,
                       filename, lineno)
    for mod, lineno in sorted(imports.items(), key=lambda kv: kv[1]):
        if mod in FORBIDDEN_IMPORTS or mod in IMPLICIT_MODULES \
                or mod in declared:
            continue
        report.add("TPL003",
                   f"import {mod!r} is neither a platform-provided module "
                   "nor declared in this template's dependencies — the "
                   "trial would die at import time on a fresh worker",
                   ERROR, filename, lineno)


# -- pass: structural contract ----------------------------------------------

def _check_structure(
        report: VerificationReport, tree: ast.Module,
        target: ast.ClassDef, classes: Dict[str, ast.ClassDef],
        methods: Dict[str, ast.FunctionDef], filename: str,
) -> Optional[Set[str]]:
    for name in REQUIRED_METHODS:
        if name not in methods:
            report.add("TPL001",
                       f"{target.name} is missing required method "
                       f"{name}() — the BaseModel contract "
                       "(docs/model-templates.md)", ERROR, filename,
                       target.lineno)
    gkc = methods.get("get_knob_config")
    if gkc is None:
        return None
    decorators = {astutil.terminal_name(d) for d in gkc.decorator_list}
    if "staticmethod" not in decorators and "classmethod" not in decorators:
        args = [a.arg for a in gkc.args.args]
        if args[:1] == ["self"]:
            report.add("TPL006",
                       "get_knob_config must be a @staticmethod — the "
                       "advisor reads the knob space from the CLASS, "
                       "before any instance exists", ERROR, filename,
                       gkc.lineno)
    return _KnobConfigEval(report, tree, classes, filename).run(gkc)


class _KnobSpace:
    """Abstract value for a knob-config dict under construction."""

    def __init__(self, names=()):
        self.names: Set[str] = set(names)


class _KnobConfigEval:
    """A tiny straight-line interpreter over ``get_knob_config`` bodies.

    Proves the knob space is *literally evaluable* without running user
    code, while accepting the idioms real templates use:

    - ``return {"lr": FloatKnob(1e-4, 1e-1)}`` — dict literal of knob
      constructors with literal args (module-level constants resolve);
    - ``cfg = dict(Parent.get_knob_config()); cfg["epochs"] =
      FixedKnob(1); return cfg`` — subclass inherits a same-file
      parent's (itself evaluable) config and pins entries.

    Anything else — a computed key, a constructor fed runtime state, a
    helper call the analyzer cannot see through — is TPL002: the
    advisor would have to *execute* the template to learn the space.
    """

    _MAX_DEPTH = 4

    def __init__(self, report: Optional[VerificationReport],
                 tree: ast.Module, classes: Dict[str, ast.ClassDef],
                 filename: str, _depth: int = 0,
                 _seen: Optional[Set[str]] = None):
        self.report = report
        self.tree = tree
        self.classes = classes
        self.filename = filename
        self.depth = _depth
        self.seen = _seen if _seen is not None else set()
        self.module_env = self._module_constants(tree)

    @staticmethod
    def _module_constants(tree: ast.Module) -> Dict[str, ast.AST]:
        # ``_DIM = 16`` / ``_DIM, _CLASSES = 8, 3`` at module level are
        # part of the literal vocabulary — templates hoist shared
        # dimensions there
        env: Dict[str, ast.AST] = {}
        for n in tree.body:
            if not isinstance(n, ast.Assign):
                continue
            for t in n.targets:
                if isinstance(t, ast.Name) and astutil.is_constant(n.value):
                    env[t.id] = n.value
                elif isinstance(t, ast.Tuple) \
                        and isinstance(n.value, ast.Tuple) \
                        and len(t.elts) == len(n.value.elts):
                    for te, ve in zip(t.elts, n.value.elts):
                        if isinstance(te, ast.Name) \
                                and astutil.is_constant(ve):
                            env[te.id] = ve
        return env

    def _fail(self, message: str, node: ast.AST) -> None:
        if self.report is not None:
            self.report.add("TPL002", message, ERROR, self.filename,
                            getattr(node, "lineno", 0))

    def run(self, gkc: ast.FunctionDef) -> Optional[Set[str]]:
        env: Dict[str, Any] = dict(self.module_env)
        spaces: List[Optional[_KnobSpace]] = []
        self._interp(gkc.body, env, spaces)
        if not spaces:
            self._fail("get_knob_config never returns a knob config", gkc)
            return None
        if any(s is None for s in spaces):
            return None
        names: Set[str] = set()
        for s in spaces:
            names |= s.names
        return names

    def _interp(self, stmts: List[ast.stmt], env: Dict[str, Any],
                spaces: List[Optional[_KnobSpace]]) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.Return):
                before = len(self.report.findings) if self.report else 0
                spaces.append(self._eval(stmt.value, env)
                              if stmt.value is not None else None)
                if spaces[-1] is None and stmt.value is not None \
                        and (self.report is None
                             or len(self.report.findings) == before):
                    # no specific finding fired — say why the whole
                    # return is opaque
                    self._fail(
                        "get_knob_config must return a statically "
                        "evaluable dict of knob constructors "
                        f"(cannot evaluate "
                        f"{ast.unparse(stmt.value)[:60]}) — the advisor "
                        "derives the search space without running user "
                        "code", stmt)
            elif isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                value = self._eval(stmt.value, env, quiet=True)
                if value is not None:
                    env[stmt.targets[0].id] = value
                elif astutil.is_constant(stmt.value):
                    env[stmt.targets[0].id] = stmt.value
                else:
                    env.pop(stmt.targets[0].id, None)  # opaque now
            elif isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Subscript):
                self._setitem(stmt.targets[0], stmt.value, env)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                continue
            elif isinstance(stmt, (ast.If, ast.For, ast.While, ast.With,
                                   ast.Try)):
                # branches are interpreted against the shared env
                # (last-wins approximation — per-entry evaluability is
                # still proven on every path that assigns)
                for body in ([stmt.body] + [getattr(stmt, "orelse", [])]
                             + [getattr(stmt, "finalbody", [])]
                             + [h.body for h in getattr(
                                 stmt, "handlers", []) or []]):
                    if body:
                        self._interp(body, env, spaces)

    def _setitem(self, target: ast.Subscript, value: ast.AST,
                 env: Dict[str, Any]) -> None:
        base, key = target.value, target.slice
        if not (isinstance(base, ast.Name)
                and isinstance(env.get(base.id), _KnobSpace)):
            return
        if not (isinstance(key, ast.Constant) and isinstance(key.value,
                                                             str)):
            self._fail("knob config keys must be string literals", target)
            env.pop(base.id, None)
            return
        bad = _non_literal_knob_expr(value, env)
        if bad is not None:
            self._fail(
                f"knob {key.value!r} is not statically evaluable "
                f"({ast.unparse(bad)[:80]}) — knob constructors must "
                "take literal arguments", value)
            env.pop(base.id, None)
            return
        env[base.id].names.add(key.value)

    def _eval(self, expr: ast.AST, env: Dict[str, Any],
              quiet: bool = False) -> Optional[_KnobSpace]:
        if isinstance(expr, ast.Name):
            value = env.get(expr.id)
            return value if isinstance(value, _KnobSpace) else None
        if isinstance(expr, ast.Dict):
            return self._eval_dict_literal(expr, env, quiet)
        if isinstance(expr, ast.Call):
            fname = astutil.terminal_name(expr.func)
            if fname == "dict":
                if not expr.args and not expr.keywords:
                    return _KnobSpace()
                if len(expr.args) == 1 and not expr.keywords:
                    return self._eval(expr.args[0], env, quiet)
                return None
            if fname == "get_knob_config" \
                    and isinstance(expr.func, ast.Attribute) \
                    and isinstance(expr.func.value, ast.Name):
                return self._eval_parent_config(expr.func.value.id)
        return None

    def _eval_dict_literal(self, expr: ast.Dict, env: Dict[str, Any],
                           quiet: bool) -> Optional[_KnobSpace]:
        space = _KnobSpace()
        ok = True
        for key, value in zip(expr.keys, expr.values):
            if not (isinstance(key, ast.Constant)
                    and isinstance(key.value, str)):
                if not quiet:
                    self._fail("knob config keys must be string literals",
                               key if key is not None else expr)
                ok = False
                continue
            space.names.add(key.value)
            bad = _non_literal_knob_expr(value, env)
            if bad is not None:
                if not quiet:
                    self._fail(
                        f"knob {key.value!r} is not statically "
                        f"evaluable ({ast.unparse(bad)[:80]}) — knob "
                        "constructors must take literal arguments", value)
                ok = False
        return space if ok else None

    def _eval_parent_config(self, class_name: str) -> Optional[_KnobSpace]:
        """``Parent.get_knob_config()`` where Parent is defined in the
        SAME file: recursively prove the parent's config evaluable and
        inherit its knob names."""
        cls = self.classes.get(class_name)
        if cls is None or self.depth >= self._MAX_DEPTH \
                or class_name in self.seen:
            return None
        parent_gkc = astutil.own_and_inherited_methods(
            cls, self.classes).get("get_knob_config")
        if parent_gkc is None:
            return None
        sub = _KnobConfigEval(None, self.tree, self.classes, self.filename,
                              _depth=self.depth + 1,
                              _seen=self.seen | {class_name})
        names = sub.run(parent_gkc)
        return _KnobSpace(names) if names is not None else None


def _non_literal_knob_expr(node: ast.AST, env: Dict[str, ast.AST],
                           depth: int = 0) -> Optional[ast.AST]:
    """None when ``node`` is an evaluable knob expression, else the
    offending sub-node."""
    if depth > 4:
        return node
    if isinstance(node, ast.Name) and isinstance(env.get(node.id), ast.AST):
        return _non_literal_knob_expr(env[node.id], env, depth + 1)
    if astutil.is_constant(node):
        return None
    if isinstance(node, ast.Call):
        name = astutil.terminal_name(node.func)
        if name is None or not (name in KNOWN_KNOB_CLASSES
                                or name.endswith("Knob")):
            return node
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name) \
                    and isinstance(env.get(arg.id), ast.AST):
                arg = env[arg.id]
            if not astutil.is_constant(arg):
                return arg
        return None
    return node


# -- pass: PopulationSpec consistency ---------------------------------------

def _check_population(
        report: VerificationReport, target: ast.ClassDef,
        classes: Dict[str, ast.ClassDef],
        methods: Dict[str, ast.FunctionDef],
        knob_names: Optional[Set[str]], filename: str,
) -> Optional[Dict[str, Any]]:
    node = astutil.class_attr_assign(target, classes, "population_spec")
    if node is None:
        return None
    if isinstance(node, ast.Constant) and node.value is None:
        return None
    lineno = getattr(node, "lineno", target.lineno)
    if not (isinstance(node, ast.Call)
            and astutil.terminal_name(node.func) == "PopulationSpec"):
        report.add("POP004",
                   "population_spec is not a literal PopulationSpec(...) "
                   "call — capability cannot be verified statically and "
                   "the worker may silently run scalar", WARN, filename,
                   lineno)
        return None
    kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
    dyn_node = node.args[0] if node.args else kwargs.get("dynamic_knobs")
    dynamic: Optional[Tuple[str, ...]] = None
    if dyn_node is not None and astutil.is_constant(dyn_node):
        try:
            value = astutil.literal_value(dyn_node)
        except ValueError:
            value = None
        if isinstance(value, (list, tuple)) and all(
                isinstance(v, str) for v in value):
            dynamic = tuple(value)
    if dynamic is None:
        report.add("POP004",
                   "PopulationSpec dynamic_knobs is not a literal "
                   "list/tuple of knob names", WARN, filename, lineno)
        return None
    max_members = 8
    mm_node = (node.args[1] if len(node.args) > 1
               else kwargs.get("max_members"))
    if mm_node is not None and astutil.is_constant(mm_node):
        try:
            max_members = int(astutil.literal_value(mm_node))
        except (TypeError, ValueError):
            pass
    missing = [m for m in POPULATION_METHODS if m not in methods]
    if missing:
        report.add("POP002",
                   f"{target.name} declares population_spec but does not "
                   f"override {', '.join(m + '()' for m in missing)} — "
                   "the worker would silently fall back to scalar trials "
                   "(sdk/model.population_capability)", ERROR, filename,
                   lineno)
        return None
    if knob_names is not None:
        rogue = [k for k in dynamic if k not in knob_names]
        if rogue:
            report.add("POP001",
                       f"dynamic knob(s) {rogue} are not in the knob "
                       "config — the vmap partitioner "
                       "(worker/vmap_partition.py) cannot bucket on a "
                       "knob the advisor never proposes", ERROR, filename,
                       lineno)
            return None
    for mname in ("train", "train_population"):
        fn = methods.get(mname)
        if fn is not None:
            _check_dynamic_knob_branching(report, fn, set(dynamic), filename)
    return {"dynamic_knobs": list(dynamic), "max_members": max_members}


# -- pass: generative capability contract (GEN00x) ---------------------------

#: decode methods a generation-capable template must override, with the
#: positional-arg count (self included) the worker calls them with —
#: sdk/model.py BaseModel.{init_kv_cache,prefill,decode_step}
GENERATION_SIGNATURES = {
    "init_kv_cache": 2,   # (self, max_slots)
    "prefill": 4,         # (self, cache, slot, prompt_ids)
    "decode_step": 4,     # (self, cache, ids, positions)
}

#: OPTIONAL paged-allocator refinement (sdk/model.py
#: GENERATION_PAGED_METHODS): arity-checked only when the template
#: overrides them — absence just means the worker serves the legacy ring
PAGED_GENERATION_SIGNATURES = {
    "init_paged_kv_cache": 3,  # (self, pool_blocks, block_tokens)
    "paged_prefill": 5,        # (self, cache, block_table, ids, start)
    "paged_decode_step": 5,    # (self, cache, ids, positions, tables)
    "kv_copy_blocks": 4,       # (self, cache, src, dst)
}

#: OPTIONAL sampling + speculative-decoding refinement (sdk/model.py
#: GENERATION_SAMPLING_METHODS / GENERATION_SPEC_METHODS): arity-checked
#: only when overridden — absence means greedy-only / plain-decode serving
SAMPLING_GENERATION_SIGNATURES = {
    "decode_step_sampled": 5,        # (self, cache, ids, positions,
                                     #  sampling)
    "decode_steps_sampled": 6,       # (self, cache, ids, positions, k,
                                     #  sampling) — optional fused
                                     #  draft-proposal burst
    "paged_decode_step_sampled": 6,  # (self, cache, ids, positions,
                                     #  tables, sampling)
    "paged_verify_step": 7,          # (self, cache, ids, positions,
                                     #  tables, draft_probs, sampling)
}


def _check_generation(
        report: VerificationReport, target: ast.ClassDef,
        classes: Dict[str, ast.ClassDef],
        methods: Dict[str, ast.FunctionDef],
        filename: str,
) -> Optional[Dict[str, Any]]:
    """The generative capability contract (mirrors _check_population):
    a template advertising ``generation_spec`` must override the three
    decode methods with the signatures the slot scheduler
    (worker/generation.py) calls. Half-wired = WARN — the capability is
    simply not advertised (generation_capability returns None), and the
    task/capability consistency check at upload turns that into a typed
    400 for TEXT_GENERATION uploads."""
    node = astutil.class_attr_assign(target, classes, "generation_spec")
    if node is None:
        return None
    if isinstance(node, ast.Constant) and node.value is None:
        return None
    lineno = getattr(node, "lineno", target.lineno)
    if not (isinstance(node, ast.Call)
            and astutil.terminal_name(node.func) == "GenerationSpec"):
        report.add("GEN003",
                   "generation_spec is not a literal GenerationSpec(...) "
                   "call — capability cannot be verified statically and a "
                   "TEXT_GENERATION upload would be refused", WARN,
                   filename, lineno)
        return None
    kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
    args = list(node.args)
    spec: Dict[str, Any] = {"eos_token_id": None, "max_context": 128,
                            "recurrent_state": False}
    for key, pos in (("eos_token_id", 0), ("max_context", 1),
                     ("recurrent_state", 2)):
        val_node = args[pos] if len(args) > pos else kwargs.get(key)
        if val_node is not None and astutil.is_constant(val_node):
            try:
                spec[key] = astutil.literal_value(val_node)
            except ValueError:
                pass
    missing = [m for m in GENERATION_SIGNATURES if m not in methods]
    if missing:
        report.add("GEN001",
                   f"{target.name} declares generation_spec but does not "
                   f"override {', '.join(m + '()' for m in missing)} — the "
                   "template is NOT generation-capable "
                   "(sdk/model.generation_capability) and cannot be "
                   "uploaded under task TEXT_GENERATION", WARN, filename,
                   lineno)
        return None
    to_check = dict(GENERATION_SIGNATURES)
    # the paged/sampling refinements are opt-in: only overridden methods
    # are checked
    to_check.update({m: n for m, n in PAGED_GENERATION_SIGNATURES.items()
                     if m in methods})
    to_check.update({m: n
                     for m, n in SAMPLING_GENERATION_SIGNATURES.items()
                     if m in methods})
    if spec["recurrent_state"]:
        # the worker also passes max_slots and the slot (sdk/model.py)
        for mname in ("init_paged_kv_cache", "paged_prefill"):
            if mname in to_check:
                to_check[mname] += 1
    for mname, n_args in to_check.items():
        fn = methods[mname]
        if fn.args.vararg is not None:
            continue  # *args swallows anything the worker passes
        # callable with exactly n_args positionals: defaults shrink the
        # required count, positional-only params count like ordinary ones
        total = len(fn.args.posonlyargs) + len(fn.args.args)
        required = total - len(fn.args.defaults)
        if not required <= n_args <= total:
            report.add("GEN002",
                       f"{mname}() accepts {required}..{total} positional "
                       f"arg(s) but the slot scheduler calls it with "
                       f"{n_args} (worker/generation.py) — the first "
                       "mid-serving call would raise TypeError", WARN,
                       filename, fn.lineno)
    return spec


def _check_dynamic_knob_branching(
        report: VerificationReport, fn: ast.FunctionDef,
        dynamic: Set[str], filename: str) -> None:
    """Members of one vmapped program share ONE compiled step — a Python
    ``if``/``while`` on a knob that differs across members would give
    each member a different trace. Flags branch tests that reference a
    dynamic-knob subscript (``knobs["lr"]``/``k.get("lr")``) or a name
    assigned from one (single-level taint, deliberately not transitive:
    deeper flows are where heuristics start lying)."""

    def knob_ref(n: ast.AST) -> bool:
        if isinstance(n, ast.Subscript):
            s = n.slice
            return isinstance(s, ast.Constant) and s.value in dynamic
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr == "get" and n.args:
            a = n.args[0]
            return isinstance(a, ast.Constant) and a.value in dynamic
        return False

    tainted: Set[str] = set()
    for node in astutil.walk_no_nested_functions(fn):
        if isinstance(node, ast.Assign) and astutil.contains(
                node.value, knob_ref):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    tainted.add(t.id)

    def test_hits(n: ast.AST) -> bool:
        return knob_ref(n) or (isinstance(n, ast.Name)
                               and isinstance(n.ctx, ast.Load)
                               and n.id in tainted)

    for node in astutil.walk_no_nested_functions(fn):
        test = None
        if isinstance(node, (ast.If, ast.While)):
            test = node.test
        elif isinstance(node, ast.IfExp):
            test = node.test
        if test is not None and astutil.contains(test, test_hits):
            report.add("POP003",
                       f"{fn.name}() branches on a dynamic knob — members "
                       "of one vmapped program must share one trace; "
                       "branch on program-shaping knobs only, or use "
                       "jnp.where/lax.cond on traced values", ERROR,
                       filename, node.lineno)


# -- pass: JAX tracing pitfalls ---------------------------------------------

def _traced_functions(tree: ast.Module) -> List[ast.AST]:
    """Function bodies that run under jax tracing: decorated with
    jit/vmap/pmap (directly or through partial), or passed by name or as
    a lambda to a tracing call (jax.jit(f), jax.lax.scan(step, ...))."""
    named: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            named[node.name] = node
    traced: List[ast.AST] = []
    seen: Set[int] = set()

    def mark(fn: Optional[ast.AST]) -> None:
        if fn is not None and id(fn) not in seen:
            seen.add(id(fn))
            traced.append(fn)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                name = astutil.terminal_name(
                    dec.func if isinstance(dec, ast.Call) else dec)
                if name in ("jit", "vmap", "pmap"):
                    mark(node)
                elif isinstance(dec, ast.Call) and name == "partial":
                    if any(astutil.terminal_name(a) in ("jit", "vmap",
                                                        "pmap")
                           for a in dec.args):
                        mark(node)
        elif isinstance(node, ast.Call):
            name = astutil.terminal_name(node.func)
            if name in _TRACING_CALLS:
                for arg in node.args:
                    if isinstance(arg, ast.Lambda):
                        mark(arg)
                    elif isinstance(arg, ast.Name) and arg.id in named:
                        mark(named[arg.id])
    return traced


def _references_static_shape(node: ast.AST) -> bool:
    """``int(x.shape[0])``-style coercions are FINE under jit — shapes
    (and dtypes/ndim) are static at trace time; only *values* are
    traced."""
    return astutil.contains(
        node, lambda n: isinstance(n, ast.Attribute)
        and n.attr in ("shape", "ndim", "dtype", "size")) is not None


def _check_jax_pitfalls(report: VerificationReport, tree: ast.Module,
                        filename: str) -> None:
    # tracing reachability is approximate (no call graph), so every
    # JAX-pitfall detector reports WARN — findings.py's invariant:
    # a heuristic must never be able to lock a working template out of
    # the platform at enforce; structural/population/sandbox passes are
    # the error-class ones
    for fn in _traced_functions(tree):
        body = fn.body if isinstance(fn, ast.Lambda) else fn
        nodes = ast.walk(body) if isinstance(fn, ast.Lambda) \
            else astutil.walk_no_nested_functions(fn)
        for node in nodes:
            if isinstance(node, ast.Call):
                tname = astutil.terminal_name(node.func)
                root = astutil.root_name(node.func)
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item" and not node.args:
                    report.add(
                        "JAX001",
                        ".item() inside a jit/vmap-traced function forces "
                        "a device sync per call (or a tracer error) — "
                        "return the array and coerce outside the traced "
                        "region", WARN, filename, node.lineno)
                elif isinstance(node.func, ast.Name) \
                        and tname in _HOST_SYNC_NAMES and node.args \
                        and not astutil.is_constant(node.args[0]) \
                        and not _references_static_shape(node.args[0]):
                    report.add(
                        "JAX001",
                        f"{tname}() on a traced value inside a jit/vmap-"
                        "traced function raises ConcretizationTypeError "
                        "at trial time — keep values as arrays under "
                        "trace", WARN, filename, node.lineno)
                elif root in ("np", "numpy", "onp") \
                        and tname in ("asarray", "array") \
                        and not (node.args
                                 and astutil.is_constant(node.args[0])):
                    # np.array([0.5, 2.0]) of constants is just a
                    # closed-over literal — only flag host pulls of
                    # non-constant (potentially traced) values
                    report.add(
                        "JAX001",
                        f"{astutil.dotted_name(node.func)}() inside a "
                        "traced function pulls the value to host memory "
                        "— use jnp inside traced code", WARN, filename,
                        node.lineno)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Attribute) and isinstance(
                            t.value, ast.Name) and t.value.id == "self":
                        report.add(
                            "JAX003",
                            f"assignment to self.{t.attr} inside a "
                            "jit/vmap-traced function — the side effect "
                            "runs once at trace time, then never again "
                            "(and leaks tracers into instance state)",
                            WARN, filename, node.lineno)
    # legacy global RNG: anywhere in the template (trial workers share a
    # process with platform code, and vmapped members share the process)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            dotted = astutil.dotted_name(node.func) or ""
            parts = dotted.split(".")
            if len(parts) >= 3 and parts[0] in ("np", "numpy") \
                    and parts[1] == "random" \
                    and parts[-1] in _LEGACY_NP_RANDOM:
                report.add(
                    "JAX002",
                    f"{dotted}() uses process-global RNG state — thread "
                    "an explicit np.random.default_rng(seed) / jax PRNG "
                    "key instead (vmapped members and forked sandbox "
                    "children share that state)", WARN, filename,
                    node.lineno)
    _check_recompile_risk(report, tree, filename)


#: methods whose bodies run once PER SERVED REQUEST — a jit() there with
#: static_argnums fed from the request recompiles on every novel value
_PER_REQUEST_METHODS = {"predict", "predict_batch", "generate"}


def _check_recompile_risk(report: VerificationReport, tree: ast.Module,
                          filename: str) -> None:
    """JAX004 — the static half of the recompile-cost work: shapes that
    force XLA to compile a fresh program per loop iteration or per
    request instead of once.

    (a) ``jax.jit``/``vmap`` applied inside a loop body to a closure
    that captures a loop-varying Python value: every iteration traces a
    new function identity with a new constant baked in. Loop variables
    derived from ``x.shape``/``ndim``/``dtype``/``size`` are exempt
    (the JAX001 carve-out carried over: shape-bucketed recompiles are a
    deliberate, bounded cost), as are constant rebinds.

    (b) ``jit(..., static_argnums=/static_argnames=)`` inside a
    per-request method: a static argument fed from request values
    recompiles per novel value, the unbounded-compile-cache shape.
    Both WARN — reachability is approximate, like every JAX detector."""
    named_funcs: Dict[str, ast.AST] = {
        n.name: n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        loop_varying: Set[str] = set()
        exempt: Set[str] = set()
        if isinstance(loop, ast.For):
            for t in ast.walk(loop.target):
                if isinstance(t, ast.Name):
                    loop_varying.add(t.id)
        for n in ast.walk(loop):
            if isinstance(n, ast.Assign):
                names = [t.id for t in n.targets
                         if isinstance(t, ast.Name)]
                if _references_static_shape(n.value) \
                        or astutil.is_constant(n.value):
                    exempt.update(names)
                else:
                    loop_varying.update(names)
            elif isinstance(n, ast.AugAssign) \
                    and isinstance(n.target, ast.Name):
                loop_varying.add(n.target.id)
        loop_varying -= exempt
        if not loop_varying:
            continue
        for n in ast.walk(loop):
            if not isinstance(n, ast.Call):
                continue
            if astutil.terminal_name(n.func) not in ("jit", "vmap",
                                                     "pmap"):
                continue
            callee = n.args[0] if n.args else None
            if isinstance(callee, ast.Lambda):
                params = {a.arg for a in callee.args.args}
                body: ast.AST = callee.body
            elif isinstance(callee, ast.Name) \
                    and callee.id in named_funcs:
                fdef = named_funcs[callee.id]
                params = {a.arg for a in fdef.args.args}
                body = fdef
            else:
                continue
            captured = sorted(
                node.id for node in ast.walk(body)
                if isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id in loop_varying and node.id not in params)
            if captured:
                report.add(
                    "JAX004",
                    f"jit/vmap inside a loop closes over loop-varying "
                    f"{', '.join(captured)!s} — every iteration traces "
                    "and compiles a fresh program with the value baked "
                    "in; hoist the jit out of the loop and pass the "
                    "value as a traced argument", WARN, filename,
                    n.lineno)
    # (b) static_argnums on the per-request path
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or fn.name not in _PER_REQUEST_METHODS:
            continue
        for n in ast.walk(fn):
            if isinstance(n, ast.Call) \
                    and astutil.terminal_name(n.func) == "jit" \
                    and any(kw.arg in ("static_argnums", "static_argnames")
                            for kw in n.keywords):
                report.add(
                    "JAX004",
                    f"jit(static_argnums=...) inside {fn.name}() marks "
                    "request-fed values static — every novel value "
                    "compiles another program and the compile cache "
                    "grows without bound; jit once at load time and "
                    "trace the value instead", WARN, filename, n.lineno)
