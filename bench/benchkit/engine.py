"""The benchmark's side of the system under test.

A configuration file's published ``model_type`` names its model family,
a module ``bench/benchkit/models/<model_type>.py`` (see :func:`adapter`)
that turns the file's keys into the program's ``ModelConfig``
(``model_config``), hands the benchmark's weights to the program in the
program's own parameter layout (``program_params``), and counts the
work one token needs (``work``, read by :mod:`benchkit.counting`).  A new
family is a new module there; nothing here changes.

This module checks each family's parameter tree leaf by leaf against the
tree the program's own ``init_params`` builds, and builds ``PagedEngine``
with only the options the configuration file's ``engine`` group names:
on one chip, or with its KV page pools striped over the ``model`` axis
of a 1 x chips mesh and the parameters replicated on every chip.  It
and the family modules are the only benchmark files that import the
program.
"""
from __future__ import annotations

import contextlib
import importlib

import numpy as np


def adapter(cfg: dict):
    """``bench/benchkit/models/<model_type>.py`` for a configuration
    file."""
    return importlib.import_module(f"benchkit.models.{cfg['model_type']}")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return adapter(cfg).model_config(cfg)


def work(cfg: dict):
    """The work count of a configuration file (see
    :mod:`benchkit.counting`)."""
    return adapter(cfg).work(cfg)


def program_params(cfg: dict, weights: dict, mcfg):
    """The family's parameter tree for the benchmark's weights, checked
    leaf by leaf against the tree the program's own ``init_params`` would
    build."""
    import jax
    from repro.models import lm
    params = adapter(cfg).program_params(weights, mcfg)
    want = jax.eval_shape(lambda k: lm.init_params(k, mcfg),
                          jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise ValueError("the program's parameter tree has changed: "
                         f"{jax.tree.structure(want)}")
    for w, p in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        if (w.shape, w.dtype) != (p.shape, p.dtype):
            raise ValueError(f"parameter leaf {p.shape} {p.dtype} where "
                             f"the program has {w.shape} {w.dtype}")
    return params


def mesh(devices):
    """The 1 x chips mesh whose ``model`` axis stripes the KV pools over
    ``devices``; ``None`` for one chip."""
    if len(devices) <= 1:
        return None
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(1, len(devices), devices=list(devices))


def replicated(tree, devices):
    """``tree`` with a whole copy on every one of ``devices`` (as it is on
    one device)."""
    m = mesh(devices)
    if m is None:
        return tree
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(tree, NamedSharding(m, PartitionSpec()))


def pool_pages(opts: dict, max_len: int, chips: int = 1) -> int:
    """The serving CLI's rule: the exact worst case of a full batch, plus
    the null page, rounded up to a multiple of the stripe."""
    n = opts["max_batch"] * -(-max_len // opts["page_size"]) + 1
    return n + (-n) % chips


# Striped pools need two workarounds of program faults (PERF.md section 7,
# "the striped pool is made whole on one device first" and "the
# program's warm-up misses the chained placement of striped windows"):
# the engine is built in host memory (:func:`stage_on_host`,
# :func:`build`) and the decode windows are warmed as they chain
# (:func:`warm_chained_windows`).  Both go when the program builds each
# stripe on its own chip and warms that placement itself.

def stage_on_host(chips: int) -> None:
    """Before JAX starts its backends: for a cell on several chips, adds
    the host's platform after the chips' (which stay the default), so
    that :func:`build` can make the striped pools in host memory."""
    import jax
    platforms = jax.config.jax_platforms
    if chips > 1 and platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")


def build(cfg: dict, mcfg, params, max_tokens: int, devices=()):
    """``PagedEngine`` with the configuration's engine options; the
    sequence bound is the traffic's longest request, rounded up to a
    page, and the pool as :func:`pool_pages` sizes it.  With more than
    one of ``devices`` the pools are striped over them (page ``p`` on
    node ``p % chips``) and the parameters replicated; otherwise the
    engine takes the default device."""
    import jax
    import jax.numpy as jnp
    from repro.serving.engine import PagedEngine
    opts = dict(cfg["engine"])
    ps = opts["page_size"]
    max_len = -(-max_tokens // ps) * ps
    chips = max(1, len(devices))
    striped = mesh(devices)
    # the engine makes its whole pool on the default device before it
    # places each chip's stripe: make it in host memory, where it fits
    host = jax.devices("cpu")[0] if striped is not None else None
    with (jax.default_device(host) if host else contextlib.nullcontext()):
        eng = PagedEngine(mcfg, params, max_len=max_len,
                          n_pages=pool_pages(opts, max_len, chips),
                          n_nodes=chips, mesh=striped, **opts)
    # and the small per-slot arrays it made there go to the default
    # device, where it makes them on one chip
    for name, value in vars(eng).items():
        if isinstance(value, jax.Array) and value.devices() == {host}:
            setattr(eng, name, jnp.asarray(np.asarray(value)))
    return eng


def smallest_chunk(schedule, chunk_tokens: int, page_size: int) -> int:
    """The shortest prefill chunk the schedule's prompts make, cut as the
    scheduler cuts them: page-aligned slices of at most ``chunk_tokens``
    from the first uncached token (0, or the end of a cached prefix)."""
    least = chunk_tokens
    for item in schedule.items:
        prefix = len(schedule.prefixes[int(item.tenant[1:])]) \
            if schedule.prefixes else 0
        for start in {0, prefix}:
            while start < len(item.prompt):
                end = min(len(item.prompt), start + chunk_tokens)
                if end < len(item.prompt) and end - end % page_size > start:
                    end -= end % page_size
                least = min(least, end - start)
                start = end
    return least


def warm(eng, schedule, vocab: int, seed: int = 7) -> None:
    """Compile, through the engine's own calls, every program the window
    can dispatch: every decode-window bucket (on striped pools also as
    the windows chain, see :func:`warm_chained_windows`); each
    power-of-two chunk bucket from the schedule's shortest chunk up to
    the chunk size (a prompt of that length is one chunk); and the page
    copy a prefix hit that ends inside a page makes.  The prefix cache
    and the counters are cleared afterwards."""
    if eng.mesh is not None:
        # first: only the first program dispatched sees the pools in the
        # form the engine first gives them
        warm_chained_windows(eng)
    rng = np.random.default_rng(seed)
    b = 1 << (smallest_chunk(schedule, eng.sched.chunk_tokens,
                             eng.page_size) - 1).bit_length()
    while b <= eng.sched.chunk_tokens:
        eng.submit(rng.integers(0, vocab, b, dtype=np.int32), 2)
        b *= 2
    eng.run()
    if eng.cache is not None:
        ps = eng.page_size
        base = rng.integers(0, vocab, 3 * ps, dtype=np.int32)
        eng.submit(base, 2)
        eng.run()
        hit = np.concatenate([base[:ps + ps // 2],
                              rng.integers(0, vocab, ps, dtype=np.int32)])
        eng.submit(hit, 2)
        eng.run()
        eng.cache.clear()
    eng.warmup_windows()
    eng.reset_metrics()


def warm_chained_windows(eng) -> None:
    """Compile every decode-window bucket of a striped engine as the
    window dispatches it after another window with no push between them:
    on the tokens and positions the last window left on the device.
    Those come back placed over the mesh, not as the host pushes them,
    and the program's ``warmup_windows`` compiles only the pushed
    placement.  Null block rows and inactive slots, as there: no page is
    written."""
    import jax.numpy as jnp
    sizes = eng.window_sizes()
    with eng._use_env():
        toks = jnp.zeros((eng.max_batch, 1), jnp.int32)
        pos = jnp.zeros((eng.max_batch,), jnp.int32)
        rows = jnp.zeros((eng.max_batch, eng.nmax), jnp.int32)
        inactive = jnp.zeros((eng.max_batch,), jnp.int32)
        for k in sizes[:1] + sizes:
            out, toks, pos, eng.pools = eng._scan(
                eng.params, toks, eng.pools, rows, pos, inactive, k=k)
            np.asarray(out)
