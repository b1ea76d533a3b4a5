"""Shared by the tests that need a flax model: the one way to build it.

Eagerly, `module.init` and `module.apply` dispatch a model one primitive
at a time, each with a small compile of its own (RAFT v4's init: 37 s
eager, 14 s as one program, 2 s as shapes). So the tests build through
`init_module` / `init_raft` (one jitted program; a RAFT's is kept for the
process, so the files one xdist worker runs share it), read shapes
through `raft_shapes`, run forwards through `jit_apply`, and call an op's
reference or entry point through `as_one_program`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dexiraft_tpu.models import RAFT

def as_one_program(fn):
    """`fn`, each call one jitted program of its own: the arrays among
    the arguments (in any pytree) traced, everything else (a radius, a
    dtype's name, `interpret`) closed over. For the ops' references and
    entry points, which eagerly are hundreds of dispatches a call."""
    def call(*args, **kwargs):
        leaves, tree = jax.tree.flatten((args, kwargs))
        traced = [isinstance(x, (jax.Array, np.ndarray)) for x in leaves]

        def run(arrays):
            arrays = iter(arrays)
            a, k = jax.tree.unflatten(
                tree, [next(arrays) if t else x
                       for x, t in zip(leaves, traced)])
            return fn(*a, **k)

        return jax.jit(run)([x for x, t in zip(leaves, traced) if t])
    return call


def init_module(module, *args, seed=0, **static):
    """`module.init(PRNGKey(seed), *args, **static)` as one program."""
    return as_one_program(module.init)(jax.random.PRNGKey(seed), *args,
                                       **static)


# the keywords of RAFT.__call__ / DexiNed.__call__ / Module.apply that
# choose the program rather than feed it
_STATIC = ("iters", "train", "freeze_bn", "test_mode", "mode", "adaptive",
           "mutable")


def jit_apply(module):
    """`module.apply` under jit, the program's choices static. A new
    function each call (jax keeps traces by the function traced): what a
    test patches (interpret mode, toy tiles) reaches its own trace and no
    other test's."""
    return jax.jit(lambda *args, **kwargs: module.apply(*args, **kwargs),
                   static_argnames=_STATIC)


def _raft_init_args(batch, h, w, with_edges):
    img = jnp.zeros((batch, h, w, 3), jnp.float32)
    return (img, img) + ((img, img) if with_edges else ())


@functools.lru_cache(maxsize=None)
def init_raft(cfg, h=64, w=64, with_edges=False, batch=1):
    """(model, variables) of RAFT(cfg) on zero frames of (batch, h, w, 3),
    `with_edges` for the variants whose edges the data supplies. Kept on
    its arguments (a frozen config hashes): do not write into the tree."""
    model = RAFT(cfg)
    return model, init_module(model, *_raft_init_args(batch, h, w, with_edges),
                              iters=1)


def raft_shapes(cfg, h=64, w=64, with_edges=False, batch=1):
    """The same tree as `init_raft`'s variables, as ShapeDtypeStructs."""
    return jax.eval_shape(
        lambda key, *a: RAFT(cfg).init(key, *a, iters=1),
        jax.random.PRNGKey(0), *_raft_init_args(batch, h, w, with_edges))
