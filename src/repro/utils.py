"""Small shared utilities."""
from __future__ import annotations

import inspect
import os

import jax
import jax.numpy as jnp

#: JAX's persistent compilation cache for runs from this checkout, where
#: ``JAX_COMPILATION_CACHE_DIR`` does not name one.  A fixed path: the
#: path is part of the cache key, so a directory that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def parse_count(s: str) -> int:
    """'1e7', '10_000', '1<<20' style counts — the CLI edge/row-count
    grammar shared by scripts/generate_dataset.py and
    scripts/fit_dataset.py."""
    s = s.replace("_", "")
    if "<<" in s:
        a, b = s.split("<<")
        return int(a) << int(b)
    return int(float(s))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself, so nothing is set here), else ``COMPILE_CACHE_DIR``.  Called
    by the entry-point scripts, never on import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def accepts_kwarg(fn, name: str) -> bool:
    """True when ``fn`` can be called with keyword ``name`` — used to
    thread optional engine kwargs (e.g. ``batch=``) through pluggable
    generator/aligner interfaces without breaking third-party ones.
    A ``**kwargs`` catch-all counts as accepting every name."""
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    if name in params:
        return True
    return any(p.kind is inspect.Parameter.VAR_KEYWORD
               for p in params.values())


def call_with_optional_kwargs(fn, *args, **optional):
    """``fn(*args)`` plus whichever of ``optional`` are non-None AND in
    ``fn``'s signature — the dispatch rule for optional engine kwargs
    across pluggable interfaces."""
    kwargs = {k: v for k, v in optional.items()
              if v is not None and accepts_kwarg(fn, k)}
    return fn(*args, **kwargs)


def tree_size(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def split_by_tree(rng, tree):
    """One PRNG key per leaf, deterministic in tree structure."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, list(keys))


def cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def assert_finite(tree, name="tree"):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            ok = bool(jnp.isfinite(leaf).all())
            assert ok, f"non-finite values in {name}{jax.tree_util.keystr(path)}"
