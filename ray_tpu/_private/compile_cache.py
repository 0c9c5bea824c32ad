"""Where XLA's persistent compile cache lives.

A train step at bench width takes tens of seconds to compile and every fresh
process would pay it again.  The rule, for every process that compiles:
where `JAX_COMPILATION_CACHE_DIR` is set, that directory is used and nothing
in code sets another; where it is not, the cache is one fixed path inside the
checkout.  The path is part of the cache key, so it is resolved from this
package's location and never from a temp dir, a pid, a session or the time.

The key covers the program's metadata too (`METADATA_ENV`).  JAX's default
key strips debug info, and `jax.named_scope`s and source lines are nothing
but debug info: two builds that differ only there would share an entry, and
the second would run the first's executable, whose profile names scopes and
lines of code that is not the code running.  The price: a change that only
moves lines in a traced file compiles cold once.

Imports no jax: workers call this at entry, before user code can import it,
so JAX reads the variables when it first loads.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
METADATA_ENV = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"


def default_dir() -> str:
    pkg_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(pkg_root, ".jax_cache")


def apply_default() -> str:
    """Put the default in the environment unless one is set; returns the
    directory in force.  A process that imported jax before the variable
    existed (an entry point called from someone else's script) gets the same
    directory, and the metadata in the key, through jax.config."""
    path = os.environ.setdefault(ENV, default_dir())
    keyed = METADATA_ENV not in os.environ
    os.environ.setdefault(METADATA_ENV, "1")
    jax = sys.modules.get("jax")
    if jax is not None:
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
        if keyed:
            jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
