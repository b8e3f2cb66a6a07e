"""Which weights may be cast ONCE, ahead of the programs that use them.

A model that computes in bf16 over fp32 parameters casts each kernel
inside every program that reads it: `leaf.astype(bf16)`, per launch,
from weights that change only at a (re)load. The cast is loop
invariant, so the engine makes it once per load (engine._load_params)
and hands the programs the result; the step then streams half the
bytes and never casts a whole embedding table to gather a few rows.

Which leaves: decided from the traced programs, not from a name, a
rank or an option. `narrowing_casts` traces every program that takes
the tree (shapes only) and looks at each leaf's consumers in the
jaxpr. A leaf is replaced iff EVERY consumer, in EVERY program, is a
`convert_element_type` to one and the same narrower dtype: the
program then sees exactly the operand it computed itself. Anything
else keeps the leaf as handed in: consumed raw anywhere (LayerNorm
scales, an fp32 router), cast to two dtypes, cast wider (an int8
leaf the program dequantizes itself), returned, or passed into a
nested jaxpr this walk has no rule for. When in doubt, keep: a
missed leaf costs bandwidth, a wrongly cast one changes the result.

The engine walks every program that takes a tree: the prompt prefill,
the decode tile and the tick's own step (engine._weight_programs); a
model is free to do with a weight in one of them what it does in no
other. The walk's traces are host time at every start (2.5-4 s a
program on the host of a v5e, PERF.md section 6, PR 31), so
`remembered_casts` keeps the decision beside the compiled programs: a
warm start reads it, as it reads its executables, and a cold one
traces. A stale decision would change results, so its key is
everything a trace reads, the source of every loaded module and of
the models' own classes included, and which entry was read is logged.
"""

import hashlib
import json
import os
import sys

import jax
import numpy as np

from elasticdl_tpu.common.log_utils import default_logger as logger

# call-like primitives whose inner jaxpr takes the equation's operands
# one for one, in order: name -> the parameter that holds the jaxpr
_CALLS = {
    "jit": "jaxpr",
    "closed_call": "call_jaxpr",
    "remat2": "jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
}

RAW = None  # a consumer that is not a plain cast


def _inner(eqn, i):
    """(jaxpr, var) that operand `i` of `eqn` becomes inside it, or
    None where the walk has no rule for the primitive."""
    name = eqn.primitive.name
    if name == "scan":
        # operands are [consts, carry, xs]; only a const is the same
        # array in every iteration
        if i >= eqn.params["num_consts"]:
            return None
        sub = eqn.params["jaxpr"]
    elif name in _CALLS:
        sub = eqn.params.get(_CALLS[name])
    else:
        return None
    sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
    invars = getattr(sub, "invars", None)
    if invars is None or len(invars) != len(eqn.invars):
        return None
    return sub, invars[i]


def _consumers(jaxpr, wanted):
    """One pass over `jaxpr` for `wanted`, {id(var): set}: add to each
    set what every consumer of that var makes of it, the dtype of a
    plain cast or RAW."""
    for out in jaxpr.outvars:
        if id(out) in wanted:
            wanted[id(out)].add(RAW)
    for eqn in jaxpr.eqns:
        sub, inside = None, {}
        for i, operand in enumerate(eqn.invars):
            uses = wanted.get(id(operand))
            if uses is None:
                continue
            if eqn.primitive.name == "convert_element_type":
                weak = eqn.params.get("weak_type", False)
                uses.add(RAW if weak
                         else np.dtype(eqn.params["new_dtype"]))
                continue
            inner = _inner(eqn, i)
            if inner is None:
                uses.add(RAW)
            else:
                sub, var = inner
                inside[id(var)] = uses
        if inside:
            _consumers(sub, inside)


def narrowing_casts(tree, programs):
    """The dtype each leaf of `tree` may be served in, as a list along
    `jax.tree.leaves(tree)`: a dtype where the leaf is to be replaced
    by its cast, None where it stays as handed in.

    `programs` is [(fn, args, argnum)]: `fn(*args)` is traced over
    shapes, and `args[argnum]` is the tree as that program takes it
    (the same structure as `tree`; arrays or ShapeDtypeStructs)."""
    def shapes(x):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x)

    n = len(jax.tree.leaves(tree))
    uses = [set() for _ in range(n)]
    for fn, args, argnum in programs:
        args = shapes(tuple(args))
        first = len(jax.tree.leaves(args[:argnum]))
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        _consumers(jaxpr, {
            id(var): seen for var, seen in zip(
                jaxpr.invars[first:first + n], uses)})
    plan = []
    for leaf, seen in zip(jax.tree.leaves(tree), uses):
        (to,) = seen if len(seen) == 1 else (RAW,)
        narrower = (to is not RAW and
                    to.itemsize < np.dtype(leaf.dtype).itemsize)
        plan.append(to if narrower else None)
    return plan


def _code_files(member):
    """The files of the code a class member runs: a function's, a
    static or class method's, a property's getter's, and those of
    whatever each wraps (flax wraps a module's methods)."""
    for attr in ("__func__", "fget"):
        member = getattr(member, attr, member)
    for _ in range(16):  # a chain of wrappers, not a cycle
        code = getattr(member, "__code__", None)
        if code is None:
            return
        yield code.co_filename
        member = getattr(member, "__wrapped__", None)


def _sources(closed_over):
    """sha256 over the Python source a trace can run: the file of
    every loaded module, and the files that define the classes of
    `closed_over` (a model loaded by path is in no module table; its
    methods' code objects still name its file). A file that cannot be
    read is hashed as that."""
    paths = {getattr(module, "__file__", None)
             for module in list(sys.modules.values())}
    for obj in closed_over:
        for cls in type(obj).__mro__:
            for member in vars(cls).values():
                paths.update(_code_files(member))
    digest = hashlib.sha256()
    for path in sorted(p for p in paths if p and p.endswith(".py")):
        try:
            with open(path, "rb") as f:
                source = f.read()
        except OSError:
            source = b"unreadable"
        digest.update(path.encode() + b"\0" + source + b"\0")
    return digest.hexdigest()


# environment that reaches a traced program or the compiler under it
_ENVIRONMENT = ("EDL_", "ELASTICDL_", "JAX_", "XLA_", "LIBTPU_", "TPU_")


def remembered_casts(closed_over, tree, programs):
    """narrowing_casts(tree, programs), remembered beside the compiled
    programs. A trace of a whole model is seconds of host time, and a
    start-up pays it per program walked; the persistent compilation
    cache already spares a warm start the compiles, and this spares it
    the walk's traces the same way, in the same directory.

    A remembered decision that is stale changes results, so the key is
    everything a trace reads: `closed_over` (the objects the programs
    close over: the models and the caller's settings) by `repr` and by
    the files that define their classes, the shapes and dtypes of the
    tree and of every argument, the source of every loaded Python
    module (this checkout, the model's zoo however it was loaded, jax
    and flax themselves), the versions of python and jaxlib, the
    backend, every jax configuration value and the environment jax,
    XLA and this package read. Any difference, an unreadable entry or
    no cache directory: trace. Which it was is logged."""
    import jaxlib

    def avals(x):
        return str(jax.tree.map(
            lambda a: (tuple(a.shape), np.dtype(a.dtype).name), x))

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return narrowing_casts(tree, programs)
    key = hashlib.sha256(json.dumps([
        repr(closed_over), avals(tree),
        [(fn.__qualname__, avals(tuple(args)), argnum)
         for fn, args, argnum in programs],
        _sources(closed_over),
        sys.version, jaxlib.__version__, jax.default_backend(),
        sorted((k, str(v)) for k, v in jax.config.values.items()),
        sorted((k, v) for k, v in os.environ.items()
               if k.startswith(_ENVIRONMENT)),
    ]).encode()).hexdigest()
    path = os.path.join(cache_dir, "edl-weight-casts-%s.json" % key)
    n = len(jax.tree.leaves(tree))
    try:
        with open(path) as f:
            names = json.load(f)
        if len(names) != n:
            raise ValueError("%d entries for %d leaves" % (len(names), n))
        plan = [None if name is None else np.dtype(name) for name in names]
        found = "read from"
    except (OSError, ValueError, TypeError):
        plan = narrowing_casts(tree, programs)
        found = "traced, kept as"
        try:
            os.makedirs(cache_dir, exist_ok=True)
            scratch = "%s.%d" % (path, os.getpid())
            with open(scratch, "w") as f:
                json.dump(
                    [None if to is None else to.name for to in plan], f)
            os.replace(scratch, path)
        except OSError:
            found = "traced, not kept as"  # no cache is no error
    cast = sum(to is not None for to in plan)
    logger.info("serving: weight casts %s %s: %d leaves cast, %d kept",
                found, path, cast, n - cast)
    return plan


def cast_leaves(tree, plan):
    """`tree` with each leaf cast as `plan` says (a list along its
    leaves, from narrowing_casts); boxes around leaves
    (nn.Partitioned and its sharding names) are kept."""
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, [
        leaf if to is None else leaf.astype(to)
        for leaf, to in zip(leaves, plan)
    ])


def tree_bytes(tree):
    """Bytes of the arrays (or shapes) of `tree`; a leaf that is
    neither (an int8 leaf's python marker) has none."""
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, "shape"))
