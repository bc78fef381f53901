"""Plain reference for the pre-activation bottleneck ResNet (He et al. 2016,
"Identity Mappings in Deep Residual Networks") that the symbolic trainer's
cells run, as MXNet's ``train_imagenet`` builds it.

Straight ``jax.numpy``/``lax`` in float32 under
``default_matmul_precision("highest")``, NCHW: 7x7/2 convolution, BatchNorm,
ReLU, 3x3/2 max pool (pad 1), four stages of bottleneck units
(BN-ReLU-1x1, BN-ReLU-3x3 carrying the stride, BN-ReLU-1x1; the first unit
of a stage projects its shortcut from the first activation with a 1x1
convolution), BN-ReLU, global average pool, fully connected with bias, mean
softmax cross entropy.  BatchNorm uses the batch's own statistics (biased
variance, eps 2e-5) and learns gain and offset.  SGD with momentum:
``mom = momentum * mom - lr * (g + wd * w)``, ``w += mom``, weight decay on
convolution and classifier weights and BatchNorm gains, none on offsets and
biases (MXNet's ``set_wd_mult``).  It imports nothing of ``mxnet_tpu`` and is
handed the benchmark's own weights under the names the program's entry
takes (``conv0_weight``, ``stage1_unit1_bn1_gamma``, ..., ``fc1_bias``).

``quant`` puts a lower precision in the reference's place for the control:
a pair ``(operand, cotangent)`` from ``benchmark/reference/precision.py``.  Both operands of every convolution and of the classifier pass through
the first on the way forward, and the gradient that flows back into each
through the second.
Units are recomputed in the backward pass (``jax.checkpoint``) so that the
256-image batch fits.
"""
import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import EXACT

BN_EPS = 2e-5
UNITS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}




def unit_plan(net):
    """(name, cin, cout, stride, dim_match) for every unit, in order."""
    plan, cin = [], 64
    for stage, n in enumerate(UNITS[net["num_layers"]]):
        cout = 256 * 2 ** stage
        for u in range(n):
            stride = 2 if (u == 0 and stage > 0) else 1
            plan.append(("stage%d_unit%d" % (stage + 1, u + 1), cin, cout,
                         stride, u != 0))
            cin = cout
    return plan


def param_shapes(net):
    """name -> shape of every learned parameter, and of the BatchNorm moving
    statistics (``aux``), as the program's symbol names them."""
    args, aux = {}, {}

    def bn(name, c):
        args[name + "_gamma"] = (c,)
        args[name + "_beta"] = (c,)
        aux[name + "_moving_mean"] = (c,)
        aux[name + "_moving_var"] = (c,)

    args["conv0_weight"] = (64, net["image_shape"][0], 7, 7)
    bn("bn0", 64)
    for name, cin, cout, _stride, match in unit_plan(net):
        mid = cout // 4
        bn(name + "_bn1", cin)
        args[name + "_conv1_weight"] = (mid, cin, 1, 1)
        bn(name + "_bn2", mid)
        args[name + "_conv2_weight"] = (mid, mid, 3, 3)
        bn(name + "_bn3", mid)
        args[name + "_conv3_weight"] = (cout, mid, 1, 1)
        if not match:
            args[name + "_sc_weight"] = (cout, cin, 1, 1)
    bn("bn1", 2048)
    args["fc1_weight"] = (net["num_classes"], 2048)
    args["fc1_bias"] = (net["num_classes"],)
    return args, aux


def conv(x, w, stride, pad, quant):
    q_in, q_back = quant
    return q_back(lax.conv_general_dilated(
        q_in(x), q_in(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")))


def bn_relu(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    y = (x - mean) * lax.rsqrt(var + BN_EPS)
    return jax.nn.relu(y * gamma[None, :, None, None] + beta[None, :, None, None])


def unit(x, p, stride, match, quant):
    a1 = bn_relu(x, p["bn1_gamma"], p["bn1_beta"])
    c1 = conv(a1, p["conv1_weight"], 1, 0, quant)
    c2 = conv(bn_relu(c1, p["bn2_gamma"], p["bn2_beta"]), p["conv2_weight"],
              stride, 1, quant)
    c3 = conv(bn_relu(c2, p["bn3_gamma"], p["bn3_beta"]), p["conv3_weight"],
              1, 0, quant)
    shortcut = x if match else conv(a1, p["sc_weight"], stride, 0, quant)
    return c3 + shortcut


def logits(params, net, images, quant=EXACT):
    x = conv(images, params["conv0_weight"], 2, 3, quant)
    x = bn_relu(x, params["bn0_gamma"], params["bn0_beta"])
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for name, _cin, _cout, stride, match in unit_plan(net):
        own = {k[len(name) + 1:]: v for k, v in params.items()
               if k.startswith(name + "_")}
        x = jax.checkpoint(unit, static_argnums=(2, 3, 4))(x, own, stride, match, quant)
    x = bn_relu(x, params["bn1_gamma"], params["bn1_beta"])
    x = jnp.mean(x, axis=(2, 3))
    q_in, q_back = quant
    return q_back(q_in(x) @ q_in(params["fc1_weight"]).T) + params["fc1_bias"]


def loss(params, net, images, labels, quant=EXACT):
    logp = jax.nn.log_softmax(logits(params, net, images, quant), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def decayed(name):
    return name.endswith("_weight") or name.endswith("_gamma")


def train_step(params, mom, net, images, labels, opt, quant=EXACT, rows=None):
    """One SGD-momentum step.  Returns the new (params, mom), the loss, and
    per leaf the norm of the gradient as the update applies it
    (``g + wd * w``).  ``rows`` plants the half-batch fault."""
    if rows is not None:
        images, labels = images[jnp.asarray(rows)], labels[jnp.asarray(rows)]
    value, grads = jax.value_and_grad(loss)(params, net, images, labels, quant)
    lr, momentum, wd = opt["learning_rate"], opt["momentum"], opt["wd"]
    new_p, new_m, gnorm = {}, {}, {}
    for k, w in params.items():
        g = grads[k] + (wd * w if decayed(k) else 0.0)
        gnorm[k] = jnp.sqrt(jnp.sum(jnp.square(g)))
        new_m[k] = momentum * mom[k] - lr * g
        new_p[k] = w + new_m[k]
    return new_p, new_m, value, gnorm
