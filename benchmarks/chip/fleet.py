"""The fleet a cell merges: a base model and K fine-tunes, made from the seed.

A configuration file (``configs/<name>.json``) lists the published
checkpoint's tensors by name and shape, the stored dtype, the block size
and the scales of the weights.  Every model of the fleet is made on the
device in one jitted call: the base is N(0, base_std^2) and fine-tune ``m``
is base + N(0, delta_std^2), both drawn in float32 from keys folded in per
(model, tensor) and rounded once to the stored dtype.  The same seed gives
the same bytes, so the plain reference can make the fleet again without
reading anything the program stored.  Tensors are made flat, in
row-major order; the host gives them their shapes when it registers them.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: model index of the base; fine-tune ``i`` (0-based) is model ``i + 1``
BASE = 0


def expert_ids(k: int) -> List[str]:
    return ["ft%d" % i for i in range(k)]


def inventory(cfg: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """[(tensor name, shape)] in the configuration's order."""
    return [(name, tuple(int(d) for d in shape)) for name, shape in cfg["tensors"]]


def stored_dtype(cfg: Dict):
    import jax.numpy as jnp

    return jnp.dtype(cfg["dtype"])


def root_key(seed: int):
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be >= 0, got %d" % seed)
    key = jax.random.key(0)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def _params(cfg: Dict) -> Tuple:
    return (tuple(int(np.prod(s)) for _, s in inventory(cfg)), cfg["dtype"],
            float(cfg["base_std"]), float(cfg["delta_std"]))


@functools.lru_cache(maxsize=None)
def _generator(sizes: Tuple[int, ...], dtype_name: str,
               base_std: float, delta_std: float):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def gen(key, model):
        base_key = jax.random.fold_in(key, BASE)
        own_key = jax.random.fold_in(key, model)
        # the base gets no delta; a fine-tune's is drawn from its own key
        dscale = jnp.where(model == BASE, 0.0, delta_std).astype(jnp.float32)
        out = []
        for i, size in enumerate(sizes):
            x = base_std * jax.random.normal(
                jax.random.fold_in(base_key, i), (size,), jnp.float32)
            d = jax.random.normal(jax.random.fold_in(own_key, i), (size,),
                                  jnp.float32)
            out.append((x + dscale * d).astype(dtype))
        return tuple(out)

    return jax.jit(gen)


def make_model(cfg: Dict, seed: int, model: int):
    """Model ``model`` of the fleet as flat device arrays, in inventory
    order (asynchronous: the call returns before the device has finished)."""
    import jax.numpy as jnp

    return _generator(*_params(cfg))(root_key(seed), jnp.int32(model))


def model_nbytes(cfg: Dict) -> int:
    itemsize = np.dtype(stored_dtype(cfg)).itemsize
    return sum(int(np.prod(s)) for _, s in inventory(cfg)) * itemsize


def register_fleet(sess, cfg: Dict, k: int, seed: int):
    """Make the base and ``k`` fine-tunes on the device, copy each to the
    host, register it through the session and ANALYZE it.  The next
    model is made on the device while the host registers the last one.
    Returns the fine-tunes' ids and the seconds of each step."""
    import jax

    inv = inventory(cfg)
    ids = ["base"] + expert_ids(k)
    split = {"make and copy": 0.0, "register": 0.0, "analyze": 0.0}
    pending = make_model(cfg, seed, 0)
    for m, mid in enumerate(ids):
        t0 = time.perf_counter()
        host = jax.device_get(pending)
        del pending
        if m + 1 < len(ids):
            pending = make_model(cfg, seed, m + 1)
        t1 = time.perf_counter()
        sess.register_model(mid, {name: flat.reshape(shape) for
                                  (name, shape), flat in zip(inv, host)})
        del host
        split["make and copy"] += t1 - t0
        split["register"] += time.perf_counter() - t1
    t0 = time.perf_counter()
    sess.analyze("base")
    for e in ids[1:]:
        sess.analyze(e, base_id="base")
    split["analyze"] = time.perf_counter() - t0
    return ids[1:], split


def fleet_arrays(cfg: Dict, seed: int, models: Sequence[int]):
    """The given models as flat device arrays, for the plain reference."""
    return [make_model(cfg, seed, m) for m in models]
