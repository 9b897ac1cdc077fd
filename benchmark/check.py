"""What decides ``correct``: the plain reference driven through the first
steps of training beside the program, and the comparison of the two.

The reference side is float32 ``jax.numpy``: the model's own reference loss,
global-norm clipping and AdamW written out here. Nothing of the program is
imported. Both moments wait in the host's memory while a gradient is worked
out and come to the device a leaf at a time for the update, so the most the
device holds at once is parameters, one gradient and one block's
activations: full width fits one chip that way.
"""

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3
# a leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone: left out of the change's comparison
MOVED_SHARE = 1e-3


def norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw_leaf(p, m, v, g, scale, t, lr, b1, b2, eps, wd):
    g = g * scale
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    update = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
    return p - lr * (update + wd * p), m, v


def reference_steps(loss_fn, params, initial, batches, opt,
                    place=lambda x: x, shardings=None):
    """Drive ``params`` (name -> float32 array, consumed) through
    ``len(batches)`` steps of clipped AdamW on ``loss_fn(params, batch)``;
    ``initial(name)`` draws a leaf again as it started. Returns the readings
    the comparison uses: each step's loss, each leaf's norm of the first
    clipped gradient, and each leaf's norm of its change over the steps.
    Over several chips ``shardings`` says how each leaf, and so its
    gradient, is cut, and ``place`` puts a batch there."""
    grad_fn = jax.jit(jax.value_and_grad(loss_fn),
                      out_shardings=shardings and (None, shardings))
    m, v = {}, {}  # on the host between steps
    losses, gnorm, grad_norms = [], None, None
    for t, batch in enumerate(batches, start=1):
        value, grads = grad_fn(params, place(batch))
        norms = {k: float(norm(g)) for k, g in grads.items()}
        total = float(np.sqrt(sum(n * n for n in norms.values())))
        clip = opt["grad_clip_norm"]
        scale = clip / max(total, clip) if clip > 0 else 1.0
        if t == 1:
            gnorm, grad_norms = total, {k: n * scale for k, n in norms.items()}
        for k in sorted(params):
            if t == 1:
                mk, vk = jnp.zeros_like(params[k]), jnp.zeros_like(params[k])
            else:
                mk = jax.device_put(m.pop(k), params[k].sharding)
                vk = jax.device_put(v.pop(k), params[k].sharding)
            params[k], mk, vk = _adamw_leaf(
                params[k], mk, vk, grads.pop(k), scale, float(t),
                opt["learning_rate"], opt["beta1"], opt["beta2"],
                opt["eps"], opt["weight_decay"])
            if t < len(batches):
                m[k], v[k] = np.asarray(mk), np.asarray(vk)
            del mk, vk
        losses.append(float(value))
    delta_norms = {k: float(norm(params.pop(k) - initial(k)))
                   for k in sorted(params)}
    return {"loss": losses, "gnorm": gnorm, "grad_norm": grad_norms,
            "delta_norm": delta_norms}


def _worst_gap(got, want, leaves, floor=None):
    """Widest gap between two norms of one leaf, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    if floor is None:
        floor = statistics.median(want[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(want[k], floor)
        if not gap <= worst:  # a nan is the worst there is
            worst, where = gap, k
    return float(worst), where


def compare(program, reference):
    """The numbers read, by name: (value, the leaf it was read on).
    Each step's loss; the gap of the whole first gradient's norm before
    the clip, where the program says it (the clip divides a common scale
    out of every leaf below, and Adam out of the change); each leaf's gap
    of the first clipped gradient's norm (a number a leaf: the small
    leaves' rounding is a hundred times the large ones', and one worst leaf
    would hide what a lower precision does to the large); the worst leaf's
    gap of the change after the steps."""
    out = {}
    for i, (got, want) in enumerate(zip(program["loss"], reference["loss"])):
        out[f"loss{i + 1}_gap"] = (abs(got - want) / abs(want), None)
    if program.get("gnorm") is not None:
        out["gnorm_gap"] = (abs(program["gnorm"] - reference["gnorm"])
                            / reference["gnorm"], None)
    grads = reference["grad_norm"]
    median = statistics.median(grads.values())
    for k in sorted(grads):
        out[f"grad_gap.{k}"] = _worst_gap(program["grad_norm"], grads, [k],
                                          floor=median)
    moved = [k for k in sorted(grads) if grads[k] >= MOVED_SHARE * median]
    out["delta_gap"] = _worst_gap(
        program["delta_norm"], reference["delta_norm"], moved)
    return out


def verdict(numbers, limits):
    """(correct, {name: [value, limit]}, names read and not compared).
    Every limit needs its number, and a value that is no number fails."""
    compared, ok = {}, True
    for name in sorted(limits):
        value = numbers.get(name, (None, None))[0]
        compared[name] = [value, limits[name]]
        if value is None or not value <= limits[name]:
            ok = False
    return ok, compared, sorted(set(numbers) - set(limits))
