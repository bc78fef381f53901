"""What the LM train step saves between its forward and its backward, and
which compiled fusions write it: for PERF.md's account of the layer scan.

    python3 tools/lm_residuals.py                 # the cell's widths
    python3 tools/lm_residuals.py --tiny          # 2 layers, d 256, S 128
    python3 tools/lm_residuals.py --hlo OUT.txt --fusions NAME [NAME ...]

Residuals are what the linearized ``make_loss_fn`` hands its tangent (jax's
``saved_residuals``) on abstract shapes, with ``kernel_platform`` taken as
``"tpu"`` so the flash kernel's residuals are the chip's; nothing is
computed, so this runs anywhere. ``stacked`` counts the residuals the layer
scan stacks, ``scan_xs`` the stacks made before it that it reads as ``xs``
(the weights cast to the compute dtype). ``--hlo`` also compiles
``make_train_step`` for the chip (attached, or a described v5e), writes its
HLO text and reports, under ``weight_stacks``, what the program does with
arrays shaped like those cast weight stacks (``weight_stack_checks``);
``--fusions`` prints each named fusion's instruction and the root of the
computation it calls (what it reads and writes). A compile is no chip run:
no time comes from this script.
"""
import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL_CONFIG = os.path.join(ROOT, "benchmark", "configs", "opt-1.3b-train.json")
CELL_TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "seq2048.json")
TINY = {"vocab": 512, "d_model": 256, "n_heads": 4, "n_layers": 2,
        "d_ff": 1024, "max_len": 128, "dtype": "bfloat16"}
TINY_BATCH = 4      # not the layer count, so a stacked residual is told apart


def cell_widths():
    """(program config, batch, seq) of ``opt-1.3b-train.seq2048``."""
    with open(CELL_CONFIG) as f:
        program = json.load(f)["program"]
    with open(CELL_TRAFFIC) as f:
        mix = json.load(f)
    return program, int(mix["batch"]), int(mix["seq_len"])


def param_shapes(cfg):
    """``init_params``' layout without its host draw of every weight."""
    import jax
    import jax.numpy as jnp

    L, d, f, H = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads
    shapes = {"embed_weight": (cfg.vocab, d), "pos_embed_weight": (cfg.max_len, d),
              "final_ln_gamma": (d,), "final_ln_beta": (d,),
              "ln1_gamma": (L, d), "ln1_beta": (L, d),
              "ln2_gamma": (L, d), "ln2_beta": (L, d),
              "attn_qkv_weight": (L, d, 3, H, d // H),
              "attn_out_weight": (L, H, d // H, d),
              "ffn_up_weight": (L, d, f), "ffn_down_weight": (L, f, d)}
    return {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}


_CALLS = ("shard_map", "jit")


def _role(jaxpr, v):
    """Where residual ``v`` of ``jaxpr`` comes from, followed into the
    shard_map and jit calls that produce it: ``stacked`` (a scan's stacked
    output: what the layer loop saves a layer), ``scan_xs`` (an array a scan
    reads as its ``xs``, made before the loop: the cast weights), ``argument``
    (a parameter passed through) or ``other``."""
    from jax.extend import core

    if isinstance(v, core.Literal):
        return "other"
    if v in jaxpr.invars:
        return "argument"
    eqn = next(e for e in jaxpr.eqns if v in e.outvars)
    i = eqn.outvars.index(v)
    if eqn.primitive.name in _CALLS:
        inner = eqn.params["jaxpr"]
        inner = getattr(inner, "jaxpr", inner)
        iv = inner.outvars[i]
        if not isinstance(iv, core.Literal) and iv in inner.invars:
            return _role(jaxpr, eqn.invars[inner.invars.index(iv)])
        return _role(inner, iv)
    if eqn.primitive.name == "scan" and i >= eqn.params["num_carry"]:
        return "stacked"
    for e in jaxpr.eqns:
        if e.primitive.name == "scan" and v in e.invars[
                e.params["num_consts"] + e.params["num_carry"]:]:
            return "scan_xs"
    return "other"


def residuals(cfg, batch, seq):
    """[(shape, dtype name, bytes, role)] of the loss's saved residuals, as
    jax's ``saved_residuals`` finds them (the outputs of the linearized
    forward that its tangent reads); ``role`` as ``_role`` gives it."""
    import math

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.mesh import train_mesh

    mesh = train_mesh(devices=jax.devices()[:1], mp=1)
    loss_fn, _ = tfm.make_loss_fn(cfg, mesh)
    tokens = jnp.zeros((batch, seq + 1), jnp.int32)
    leaves, tree = jax.tree.flatten(param_shapes(cfg))

    def loss(*leaves):
        return loss_fn(jax.tree.unflatten(tree, leaves), tokens)

    closed, shapes = jax.make_jaxpr(lambda *a: jax.linearize(loss, *a),
                                    return_shape=True)(*leaves)
    jaxpr = closed.jaxpr
    n_res = len(jax.tree.leaves(shapes[1]))
    out = []
    for v in jaxpr.outvars[len(jaxpr.outvars) - n_res:]:
        aval = v.aval
        n = math.prod(aval.shape) * aval.dtype.itemsize
        out.append((tuple(aval.shape), aval.dtype.name, int(n), _role(jaxpr, v)))
    return out


def summary(res, cfg, batch, seq):
    """Totals, the bytes the scan stacks and those it reads as ``xs``, the
    activation-sized float32 and the bool residuals in and outside the
    scan's stacks, the groups largest first."""
    groups, count = collections.Counter(), collections.Counter()
    totals = collections.Counter()
    for shape, dtype, n, role in res:
        where = role if role in ("stacked", "scan_xs") else "other"
        key = "%s[%s] %s" % (dtype, ",".join(map(str, shape)), where)
        groups[key] += n
        count[key] += 1
        totals["%s_bytes" % where] += n
        inside = "stacked" if role == "stacked" else "other"
        if dtype == "bool":
            totals["bool_%s" % inside] += 1
        if dtype == "float32" and shape[-3:-1] == (batch, seq) \
                and shape[-1] in (cfg.d_model, cfg.d_ff):
            totals["float32_activations_%s" % inside] += 1
    return {"total_bytes": sum(r[2] for r in res), "n_residuals": len(res),
            **{k: totals[k] for k in (
                "stacked_bytes", "scan_xs_bytes", "float32_activations_stacked",
                "bool_stacked", "float32_activations_other", "bool_other")},
            "groups": [{"what": k, "count": count[k], "bytes": b}
                       for k, b in groups.most_common()]}


def compile_text(cfg, batch, seq):
    """``make_train_step``'s compiled HLO for one chip: the attached one,
    else a described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel.spmd import functional_optimizer

    with open(CELL_CONFIG) as f:
        opt = json.load(f)["optimizer"]
    if jax.default_backend() == "tpu":
        devices = jax.devices()
    else:
        from jax.experimental import topologies
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    mesh = Mesh(devices[:1], ("dp",))
    opt = functional_optimizer(**opt)
    step, _ = tfm.make_train_step(cfg, mesh, optimizer=opt)
    rep = NamedSharding(mesh, P())
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep)
              for k, v in param_shapes(cfg).items()}
    state = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep),
                         jax.eval_shape(opt.init, params))
    carry = (params, state, jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp", None)))
    compiled = step.lower(carry, tokens).compile()
    return compiled.as_text(), compiled.memory_analysis()


def fusion_lines(text, names):
    """name -> (its instruction, the root of the computation it calls, or
    None), or None where the program has no such instruction."""
    out = {}
    for name in names:
        m = re.search(r"^\s*(?:ROOT )?%" + re.escape(name) + r" = .*$", text, re.M)
        if not m:
            out[name] = None
            continue
        line, root = m.group(0).strip(), None
        calls = re.search(r"calls=%([\w.\-]+)", line)
        body = calls and re.search(r"^%" + re.escape(calls.group(1)) + r" .*?\{\n(.*?)\n\}",
                                   text, re.M | re.S)
        if body:
            root = next((ln.strip() for ln in body.group(1).splitlines()
                         if ln.strip().startswith("ROOT")), None)
        out[name] = (line, root)
    return out


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")


def _instructions(text):
    """[(computation, name, dtype, dims, opcode, operands, op_name)] of the
    HLO text; dtype and dims are None for a tuple-shaped result."""
    out, comp = [], None
    for line in text.splitlines():
        head = _HEADER.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, typ, opcode, rest = m.groups()
        arr = re.match(r"(\w+)\[([\d,]*)\]", typ)
        dtype, dims = (arr.group(1), tuple(int(d) for d in arr.group(2).split(",") if d)) \
            if arr else (None, None)
        operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
        op_name = re.search(r'op_name="([^"]*)"', rest)
        out.append((comp, name, dtype, dims, opcode, operands,
                    op_name.group(1) if op_name else ""))
    return out


def weight_stack_checks(text, stacks):
    """What the compiled step does with arrays shaped like the stacked
    matrices the layer scan casts (``stacks``: their dims): the forward
    loop's ``dynamic-update-slice``s into such a stack (each layer's cast
    saved again), the standalone ``broadcast``s (zeroed gradient stacks) and
    ``convert``s (casts outside any fusion: to the compute dtype before the
    loop; one to float32 would be a gradient stack widened on its own), and
    the dtype of each gradient stack the optimizer's fusions read straight
    from the backward loop. Standalone: outside every fused computation."""
    ins = _instructions(text)
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    top = {name: (dtype, dims, op, operands, op_name)
           for comp, name, dtype, dims, op, operands, op_name in ins
           if comp not in fused}
    report = {"forward_weight_updates": [], "standalone_broadcasts": [],
              "standalone_converts": [], "optimizer_grad_dtypes": {}}
    for comp, name, dtype, dims, op, operands, op_name in ins:
        if dims not in stacks:
            continue
        if op == "dynamic-update-slice" and "jvp()/while" in op_name \
                and "transpose(" not in op_name:
            report["forward_weight_updates"].append("%s %s" % (name, dtype))
        if comp in fused:
            continue
        if op == "broadcast":
            report["standalone_broadcasts"].append("%s %s" % (name, dtype))
        if op == "convert":
            report["standalone_converts"].append("%s -> %s" % (name, dtype))
    for name, (_, _, op, operands, op_name) in top.items():
        if op != "fusion" or "mx.opt.update" not in op_name:
            continue
        for o in operands:
            dtype, dims, oop, _, oname = top.get(o, (None,) * 5)
            if dims in stacks and oop == "get-tuple-element" \
                    and "transpose(jvp())/while" in oname:
                report["optimizer_grad_dtypes"][name] = dtype
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true", help="2 layers, d 256, ff 1024, S 128")
    ap.add_argument("--hlo", help="also compile the step for the chip (a described "
                    "v5e where none is attached); write its HLO here")
    ap.add_argument("--fusions", nargs="*", default=(),
                    help="fusion names to print from the compiled HLO")
    args = ap.parse_args(argv)

    import mxnet_tpu.kernels  # noqa: F401  (loads kernels.flash_attention)
    from mxnet_tpu.models import transformer as tfm

    for name in ("mxnet_tpu.models.transformer", "mxnet_tpu.kernels.flash_attention"):
        sys.modules[name].kernel_platform = lambda: "tpu"
    if args.tiny:
        program, batch, seq = dict(TINY), TINY_BATCH, TINY["max_len"]
    else:
        program, batch, seq = cell_widths()
    cfg = tfm.TransformerConfig(**program)
    res = residuals(cfg, batch, seq)
    report = summary(res, cfg, batch, seq)
    report["widths"] = {"batch": batch, "seq": seq, **program}
    if args.hlo:
        text, mem = compile_text(cfg, batch, seq)
        with open(args.hlo, "w") as f:
            f.write(text)
        report["compiled"] = {"temp_bytes": mem.temp_size_in_bytes,
                              "argument_bytes": mem.argument_size_in_bytes,
                              "output_bytes": mem.output_size_in_bytes,
                              "alias_bytes": mem.alias_size_in_bytes}
        stacks = {s.shape for k, s in param_shapes(cfg).items() if k in tfm._SCAN_CAST}
        report["weight_stacks"] = weight_stack_checks(text, stacks)
        for name, found in fusion_lines(text, args.fusions).items():
            print("== %s" % name)
            if found is None:
                print("   (not in this program)")
                continue
            print("   " + found[0][:600])
            print("   root: %s" % (found[1] or "")[:600])
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
