"""Plain reference of the ResNet-50 training cell: ResNet v1.5's forward
with batch statistics, the loss, its gradients and SGD with momentum in
straightforward ``jax.numpy`` / ``lax.conv_general_dilated``, float32 at
``highest`` precision. It imports nothing of the program; weights and
images come from ``seeded``, from the seed.

BatchNorm couples the rows of a batch, so the batch is not cut: each
bottleneck block is a ``jax.checkpoint`` instead, and the backward computes
a block's forward again — that is what makes 128 images fit in float32.

``variant``: ``fp8`` rounds both operands of every convolution and of the
dense head to float8_e4m3 (the control); ``half_batch`` trains on the first
half of each batch; ``bf16`` rounds every convolution's operands and result
to bfloat16 — not a control but a second witness: what the configuration's
own precision does to these numbers, computed without the program.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

VARIANTS = ("reference", "fp8", "half_batch", "bf16")


def _blocks(cfg):
    """(block index, width, stride, has projection) of every bottleneck."""
    out, k, cin = [], 0, cfg["num_filters"]
    for i, count in enumerate(cfg["stage_sizes"]):
        width = cfg["num_filters"] * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out.append((k, cin, width, stride, cin != 4 * width))
            cin = 4 * width
            k += 1
    return out, cin


def leaf_specs(cfg: dict):
    """(name, shape, init) of every trained parameter, in a fixed order;
    names are the program's own paths, slash-joined."""
    def conv(name, kh, kw, ci, co):
        return (name + "/kernel", (kh, kw, ci, co),
                ("normal", float(np.sqrt(2.0 / (kh * kw * ci)))))

    def norm(name, c, last=False):
        lo, hi = (0.1, 0.5) if last else (0.5, 1.5)
        return [(name + "/scale", (c,), ("uniform", lo, hi)),
                (name + "/bias", (c,), ("normal", 0.1))]

    f = cfg["num_filters"]
    specs = [conv("conv_init", 7, 7, 3, f)] + norm("bn_init", f)
    blocks, width = _blocks(cfg)
    for k, cin, w, _, proj in blocks:
        b = f"BottleneckBlock_{k}"
        specs += [conv(f"{b}/Conv_0", 1, 1, cin, w)]
        specs += norm(f"{b}/BatchNorm_0", w)
        specs += [conv(f"{b}/Conv_1", 3, 3, w, w)]
        specs += norm(f"{b}/BatchNorm_1", w)
        specs += [conv(f"{b}/Conv_2", 1, 1, w, 4 * w)]
        specs += norm(f"{b}/BatchNorm_2", 4 * w, last=True)
        if proj:
            specs += [conv(f"{b}/conv_proj", 1, 1, cin, 4 * w)]
            specs += norm(f"{b}/norm_proj", 4 * w)
    specs += [("Dense_0/kernel", (width, cfg["num_classes"]),
               ("normal", float(np.sqrt(2.0 / width)))),
              ("Dense_0/bias", (cfg["num_classes"],), ("zeros",))]
    return specs


def norm_names(cfg: dict):
    """Every BatchNorm of the model and its channels (for the running
    statistics, which are state and not trained)."""
    return [(n[:-len("/scale")], shape[0])
            for n, shape, _ in leaf_specs(cfg) if n.endswith("/scale")]


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


def _b16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _conv(x, w, stride, fp8):
    """``fp8``: False, True (float8 operands) or "bf16" (bfloat16 operands
    and result)."""
    if fp8 == "bf16":
        x, w = _b16(x), _b16(w)
    elif fp8:
        x, w = _q8(x), _q8(w)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return _b16(y) if fp8 == "bf16" else y


def _bn(x, p, name, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return ((x - mean) * lax.rsqrt(var + eps) * p[name + "/scale"]
            + p[name + "/bias"])


def _forward_loss(p, images, labels, cfg, traffic, fp8):
    eps = cfg["bn_epsilon"]
    x = _conv(images, p["conv_init/kernel"], 2, fp8)
    x = jax.nn.relu(_bn(x, p, "bn_init", eps))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    blocks, _ = _blocks(cfg)
    for k, _, _, stride, proj in blocks:
        b = f"BottleneckBlock_{k}"

        @jax.checkpoint
        def block(x, p, b=b, stride=stride, proj=proj):
            y = _conv(x, p[f"{b}/Conv_0/kernel"], 1, fp8)
            y = jax.nn.relu(_bn(y, p, f"{b}/BatchNorm_0", eps))
            y = _conv(y, p[f"{b}/Conv_1/kernel"], stride, fp8)
            y = jax.nn.relu(_bn(y, p, f"{b}/BatchNorm_1", eps))
            y = _conv(y, p[f"{b}/Conv_2/kernel"], 1, fp8)
            y = _bn(y, p, f"{b}/BatchNorm_2", eps)
            r = x
            if proj:
                r = _conv(x, p[f"{b}/conv_proj/kernel"], stride, fp8)
                r = _bn(r, p, f"{b}/norm_proj", eps)
            return jax.nn.relu(r + y)

        x = block(x, {n: a for n, a in p.items() if n.startswith(b + "/")})
    x = jnp.mean(x, axis=(1, 2))
    w = p["Dense_0/kernel"]
    if fp8 is True:
        x, w = _q8(x), _q8(w)
    logits = jnp.dot(x, w, precision=lax.Precision.HIGHEST) \
        + p["Dense_0/bias"]
    classes = cfg["num_classes"]
    ls = traffic["label_smoothing"]
    target = jax.nn.one_hot(labels, classes) * (1.0 - ls) + ls / classes
    loss = jnp.mean(-jnp.sum(target * jax.nn.log_softmax(logits), axis=-1))
    l2 = sum(jnp.sum(jnp.square(a)) for n, a in p.items()
             if n.endswith("/kernel"))
    return loss + traffic["weight_decay"] * 0.5 * l2


@functools.lru_cache(maxsize=None)
def _step_program(cfg_json: str, traffic_json: str, fp8):
    cfg, traffic = json.loads(cfg_json), json.loads(traffic_json)
    opt = traffic["optimizer"]

    def step(p, trace, images, labels):
        loss, g = jax.value_and_grad(_forward_loss)(
            p, images.astype(jnp.float32), labels, cfg, traffic, fp8)
        trace = {n: g[n] + opt["momentum"] * trace[n] for n in p}
        new = {n: p[n] - opt["learning_rate"] * trace[n] for n in p}
        norms = {n: jnp.sqrt(jnp.sum(jnp.square(g[n]))) for n in p}
        return new, trace, loss, norms

    return jax.jit(step, donate_argnums=(0, 1))


def run(cfg: dict, traffic: dict, seed: int, chips: int, seeded,
        steps: int = 3, variant: str = "reference", devices=None,
        log=None) -> dict:
    """Follow the cell's first ``steps`` steps from the seed (one chip's
    rows; this cell has no exchange)."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r} not in {VARIANTS}")
    if chips != 1:
        raise ValueError("this reference follows one chip's batch")
    with jax.default_matmul_precision("highest"):
        specs = leaf_specs(cfg)
        key = seeded.key(seed)
        p0 = jax.jit(lambda k: seeded.leaves(k, specs))(key)
        p = dict(p0)
        trace = {n: jnp.zeros_like(a) for n, a in p.items()}
        step = _step_program(json.dumps(cfg, sort_keys=True),
                             json.dumps(traffic, sort_keys=True),
                             {"fp8": True, "bf16": "bf16"}.get(variant,
                                                               False))
        rows = traffic["batch_per_chip"]
        make = jax.jit(seeded.images, static_argnums=(3, 4, 5))
        losses, grad_norm = [], None
        p = {n: a + 0 for n, a in p.items()}  # p0 stays; p is donated
        for s in range(steps):
            images, labels = make(key, 0, s, rows, cfg["image_size"],
                                  cfg["num_classes"])
            if variant == "half_batch":
                images, labels = images[:rows // 2], labels[:rows // 2]
            p, trace, loss, norms = step(p, trace, images, labels)
            losses.append(float(np.asarray(loss)))
            if s == 0:
                grad_norm = {n: float(np.asarray(v))
                             for n, v in norms.items()}
        change = {n: float(np.asarray(jnp.sqrt(jnp.sum(
            jnp.square(p[n] - p0[n]))))) for n in p}
        return {"loss": losses, "grad_norm": grad_norm,
                "change_norm": change}
