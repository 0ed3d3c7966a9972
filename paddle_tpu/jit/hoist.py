"""``hoisted_jit``: ``jax.jit`` for functions that CLOSE OVER large arrays.

``jax.jit`` lowers every array a function captured from its closure as
a literal constant of the program: a decode step that closes over its
model's weight tree becomes StableHLO text the size of the weights, an
executable that carries a second copy of them, and — past 2 GB — a
program that cannot be lowered at all. The decode bundles
(``models/generation.py``) and the loops built over them
(``inference/decode_loop.py``, the serving tick) are such closures by
design: their callers pass ``step_fn(x, caches, t)`` around without
knowing what it captured.

``hoisted_jit(fn)`` keeps that calling convention and moves the
captured arrays to the other side of the jit boundary: the first call
per argument signature traces ``fn`` to a jaxpr (``jax.make_jaxpr``
exposes everything it captured as ``.consts``), and the compiled
program is ``jit(lambda consts, *args: eval_jaxpr(jaxpr, consts,
*args))`` — the captured arrays ride as RUNTIME ARGUMENTS, by
reference, so N programs over one closure share one copy of them.
"""
import jax
import jax.numpy as jnp

__all__ = ["hoisted_jit"]


def _signature(args):
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple(jax.typeof(leaf) for leaf in leaves)


def _returned_in_place(args, donate_argnums, out_shape):
    """``out_shardings`` that hand every donated, mesh-placed argument
    back on the placement it came in with (None: nothing to pin). A
    donated tree reappears in the output (the KV caches of a decode
    step); with the weights riding as SHARDED arguments GSPMD would
    otherwise lay the new caches out after them — a replicated pool came
    back split on heads and head_dim — so donation could not alias and
    the next call would meet buffers its program was not compiled for.
    The output run is found by the donated leaves' shapes and dtypes, in
    order, and pinned only when that match is unique."""
    out_leaves, out_tree = jax.tree_util.tree_flatten(out_shape)
    outs = [(o.shape, o.dtype) for o in out_leaves]
    pinned = [None] * len(outs)
    for i in donate_argnums:
        leaves = jax.tree_util.tree_leaves(args[i])
        want = [(jnp.shape(a), jnp.result_type(a)) for a in leaves]
        starts = [j for j in range(len(outs) - len(want) + 1)
                  if outs[j:j + len(want)] == want]
        if not leaves or len(starts) != 1:
            continue
        for j, a in enumerate(leaves, starts[0]):
            if isinstance(a, jax.Array) and len(a.sharding.device_set) > 1:
                pinned[j] = a.sharding
    if not any(pinned):
        return None
    return jax.tree_util.tree_unflatten(out_tree, pinned)


class _Program:
    """One traced signature: the jitted ``(consts, *args)`` program and
    the captured arrays it is always called with."""

    __slots__ = ("jitted", "consts")

    def __init__(self, fn, args, donate_argnums):
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        out_tree = jax.tree_util.tree_structure(out_shape)
        jaxpr = closed.jaxpr

        def run(consts, *call_args):
            flat = jax.tree_util.tree_leaves(call_args)
            out = jax.core.eval_jaxpr(jaxpr, consts, *flat)
            return jax.tree_util.tree_unflatten(out_tree, out)

        # the program is called what the function it wraps is called
        # (``jit_<name>`` in a profiler trace and in the HLO), on the
        # jit path and through ``lower().compile()`` alike. A constant
        # name: an id or a width in it would miss the persistent
        # compile cache on every start
        run.__name__ = run.__qualname__ = getattr(fn, "__name__", "run")
        self.consts = closed.consts
        self.jitted = jax.jit(
            run, donate_argnums=tuple(i + 1 for i in donate_argnums),
            out_shardings=_returned_in_place(args, donate_argnums,
                                             out_shape))


class _Bound:
    """A lowered or compiled ``(consts, *args)`` stage presented with
    the wrapped function's own signature: ``compile()`` and ``__call__``
    supply the captured arrays, everything else (``as_text``,
    ``cost_analysis``, ``memory_analysis``…) is the JAX stage's own."""

    __slots__ = ("_stage", "_consts")

    def __init__(self, stage, consts):
        self._stage = stage
        self._consts = consts

    def compile(self, *args, **kwargs):
        return _Bound(self._stage.compile(*args, **kwargs), self._consts)

    def __call__(self, *args):
        return self._stage(self._consts, *args)

    def __getattr__(self, name):
        return getattr(self._stage, name)


class _HoistedJit:
    def __init__(self, fn, donate_argnums=()):
        self._fn = fn
        self._donate = tuple(donate_argnums)
        self._programs = {}

    def _program(self, args):
        # the key is what jax.jit itself would retrace on (pytree
        # structure + avals). Trace-time config (matmul precision, x64)
        # is read once per signature: set it before the first call.
        key = _signature(args)
        prog = self._programs.get(key)
        if prog is None:
            prog = self._programs[key] = _Program(self._fn, args,
                                                  self._donate)
        return prog

    def __call__(self, *args):
        prog = self._program(args)
        return prog.jitted(prog.consts, *args)

    def lower(self, *args):
        prog = self._program(args)
        return _Bound(prog.jitted.lower(prog.consts, *args), prog.consts)


def hoisted_jit(fn, donate_argnums=()):
    """``jax.jit(fn, donate_argnums=...)`` with every array ``fn``
    captured from its closure passed as a runtime argument instead of
    baked into the program. Positional arguments only; supports
    ``.lower(*args).compile()`` like a jitted function (the compiled
    stage is called with ``fn``'s own arguments). The program carries
    ``fn``'s name: give ``fn`` one fit for a trace."""
    return _HoistedJit(fn, donate_argnums)
