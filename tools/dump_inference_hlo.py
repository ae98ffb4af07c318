"""Evidence for the "XLA subsumes the reference's inference fusion passes"
claim (reference: python/paddle/fluid/transpiler/inference_transpiler.py:73-239
fuses conv+bn, conv+bias, conv+relu, conv+eltwise, bn+relu as graph
rewrites; paddle/fluid/framework/ir/*_fuse_pass.cc is the general
framework).  On TPU those rewrites are the compiler's job: this tool
compiles an inference ResNet-50 block-slice, dumps the OPTIMIZED HLO, and
counts how the patterns landed:

* conv+bias / conv+eltwise / conv+relu / bn+relu — elementwise consumers
  fused into the convolution's output fusion;
* conv+bn — after InferenceTranspiler's constant fold there is no BN op
  left to fuse at all (the fold also shrinks the exported model).

Prints a summary plus the fusion-computation census; writes the full HLO
next to it for inspection.  Run on the TPU backend for the real evidence
(the CPU backend uses different fusion heuristics).

Usage: python tools/dump_inference_hlo.py [--out FILE] [--no-fold]
"""
from __future__ import annotations

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_resnet_infer_program():
    """Inference ResNet-50 Program + initialized state + predict var —
    shared by the fusion census and the int8 census."""
    import paddle_tpu as fluid
    from paddle_tpu.jax_bridge import init_state

    with fluid.unique_name.guard():
        main = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(main, startup):
            image = fluid.layers.data(name="data", shape=[3, 224, 224], dtype="float32")
            from paddle_tpu.models.resnet import resnet_imagenet

            predict = resnet_imagenet(image, class_dim=1000, depth=50, is_train=False)
        infer = main.clone(for_test=True)
    return infer, init_state(startup), predict


def compile_and_dump(fn, state, feeds, out_path):
    """jit-compile, write the optimized HLO text to out_path, return it."""
    import jax

    compiled = jax.jit(fn).lower(state, feeds).compile()
    texts = [m.to_string() for m in compiled.runtime_executable().hlo_modules()] \
        if hasattr(compiled, "runtime_executable") else [compiled.as_text()]
    hlo = "\n\n".join(texts)
    with open(out_path, "w") as f:
        f.write(hlo)
    return hlo


def build_infer_fn(fold_bn):
    import paddle_tpu as fluid
    from paddle_tpu.jax_bridge import program_to_fn

    infer, state, predict = build_resnet_infer_program()
    if fold_bn:
        from paddle_tpu.transpiler.inference_transpiler import InferenceTranspiler

        scope = fluid.global_scope()
        for k, v in state.items():
            scope.vars[k] = v
        infer = InferenceTranspiler().transpile(infer, scope=scope)
        state = {k: scope.vars[k] for k in
                 (v.name for v in infer.list_vars() if v.persistable)
                 if scope.vars.get(k) is not None}
    fn = program_to_fn(infer, [predict.name], is_test=True)
    return fn, state


def analyze(hlo_text):
    """Census of fused convolutions in optimized HLO.

    Two complementary views:
    * per-computation: for each computation containing a convolution,
      which elementwise ops ride along (add = bias/eltwise, maximum =
      relu) — on TPU convs get their own fusion computations, so this
      shows the conv+bias+relu folding directly;
    * ENTRY-level: standalone (unfused) add/maximum instructions at the
      top scope.  Zero standalone elementwise ops means every bias-add /
      relu / eltwise the reference's fuse passes targeted lives inside a
      fusion — nothing re-reads activations from HBM for them."""
    # computation name -> body
    comps = {}
    cur, body = None, []
    for line in hlo_text.splitlines():
        m = re.match(r"^(%?[\w\.\-]+) (?:\([^)]*\))? ?->.*{$", line.strip())
        if m or (line.startswith("ENTRY") and line.rstrip().endswith("{")):
            if cur is not None:
                comps[cur] = body
            cur = m.group(1) if m else "ENTRY"
            body = []
        elif line.strip() == "}":
            if cur is not None:
                comps[cur] = body
            cur, body = None, []
        elif cur is not None:
            body.append(line)

    conv_fusions = {"with_add": 0, "with_max": 0, "with_add_and_max": 0,
                    "bare": 0, "total": 0}
    for name, body in comps.items():
        text = "\n".join(body)
        if "convolution" not in text:
            continue
        conv_fusions["total"] += 1
        has_add = re.search(r"\badd\(|\badd\.", text) is not None
        has_max = re.search(r"\bmaximum\(|\bmaximum\.", text) is not None
        if has_add and has_max:
            conv_fusions["with_add_and_max"] += 1
        elif has_add:
            conv_fusions["with_add"] += 1
        elif has_max:
            conv_fusions["with_max"] += 1
        else:
            conv_fusions["bare"] += 1
    entry = comps.get("ENTRY", [])
    entry_text = "\n".join(entry)
    entry_census = {
        "standalone_add": len(re.findall(r"= \S+ add\(", entry_text)),
        "standalone_maximum": len(re.findall(r"= \S+ maximum\(", entry_text)),
        "standalone_multiply": len(re.findall(r"= \S+ multiply\(", entry_text)),
        "convolutions": len(re.findall(r"\bconvolution\(", entry_text)),
        "fusions": len(re.findall(r"\bfusion\(", entry_text)),
    }
    counts = {
        "batch_norm_ops": len(re.findall(r"batch-norm", hlo_text)),
        "rsqrt_ops": len(re.findall(r"\brsqrt", hlo_text)),
        "fusion_instructions": len(re.findall(r"\bfusion\(", hlo_text)),
        "convolutions": len(re.findall(r"\bconvolution[\(.]", hlo_text)),
        "copies": len(re.findall(r"\bcopy\(", hlo_text)),
    }
    return conv_fusions, counts, entry_census


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="INFERENCE_HLO.txt")
    ap.add_argument("--no-fold", action="store_true",
                    help="skip the conv+bn constant fold first")
    ap.add_argument("--skip-int8", action="store_true",
                    help="skip the int8-program census")
    args = ap.parse_args(argv)

    import jax

    fn, state = build_infer_fn(fold_bn=not args.no_fold)
    x = np.zeros((8, 3, 224, 224), np.float32)
    hlo = compile_and_dump(fn, state, {"data": x}, args.out)

    conv_fusions, counts, entry_census = analyze(hlo)
    backend = jax.devices()[0].platform
    print("backend=%s  optimized HLO -> %s (%d KiB)"
          % (backend, args.out, len(hlo) // 1024))
    print("instruction census: %s" % counts)
    print("conv-computation census: %s" % conv_fusions)
    print("ENTRY-scope census: %s" % entry_census)
    fused = conv_fusions["with_add"] + conv_fusions["with_add_and_max"] + conv_fusions["with_max"]
    print("=> %d/%d conv computations carry fused elementwise consumers "
          "(bias/eltwise-add and/or relu-maximum); %d bare"
          % (fused, conv_fusions["total"], conv_fusions["bare"]))
    bare_elt = entry_census["standalone_add"] + entry_census["standalone_maximum"]
    print("=> %d standalone (unfused) add/maximum instructions at ENTRY "
          "scope%s" % (bare_elt,
                       " — every bias/relu/eltwise is inside a fusion"
                       if bare_elt == 0 else " — candidates for a fold"))
    if counts["batch_norm_ops"] == 0:
        print("=> zero batch-norm instructions survive (conv+bn folded "
              "by InferenceTranspiler%s)"
              % ("" if not args.no_fold else " -- UNEXPECTED with --no-fold"))

    if not args.skip_int8:
        int8_census(args.out + ".int8")
    return 0


def int8_census(out_path):
    """Census the int8-transpiled inference ResNet-50: evidence that the
    quantized convs execute as int8 MXU matmuls (s8 dot_generals with s32
    accumulation), not as slow integer convolutions (PERF.md round 5:
    the direct integer conv measured ~1% of bf16 throughput)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.contrib.quantize import Int8InferenceTranspiler
    from paddle_tpu.contrib.quantize import int8_inference as int8_mod
    from paddle_tpu.jax_bridge import program_to_fn

    # On TPU, census the REAL auto dispatch (matmul + thin-channel
    # dequant).  Off-TPU auto picks the direct conv for every layer,
    # which would make this structural check a guaranteed false alarm —
    # pin the matmul decomposition there instead.
    on_tpu = jax.default_backend() == "tpu"
    prev_impl = int8_mod.INT8_CONV_IMPL
    if not on_tpu and prev_impl == "auto":
        int8_mod.INT8_CONV_IMPL = "matmul"

    infer, state, predict = build_resnet_infer_program()
    s = dict(state)
    Int8InferenceTranspiler().transpile(infer, s)
    state_q = dict(state)
    state_q.update({k: np.asarray(v) for k, v in s.items()
                    if k.endswith((".int8", ".scale"))})
    state_q = {k: (jnp.asarray(v, jnp.bfloat16)
                   if hasattr(v, "dtype") and v.dtype == np.float32
                   and not k.endswith(".scale") else v)
               for k, v in state_q.items()}
    fn = program_to_fn(infer, [predict.name], is_test=True)
    x = jnp.asarray(np.zeros((8, 3, 224, 224), np.float32), jnp.bfloat16)
    hlo = compile_and_dump(fn, state_q, {"data": x}, out_path)

    s8_dots = len(re.findall(r"= s32\[[^\]]*\]\S* dot\([^)]*\)", hlo))
    s8_convs = len(re.findall(r"= s32\[[^\]]*\]\S* convolution\(", hlo))
    s8_tensors = len(re.findall(r"s8\[", hlo))
    print("int8 census (%s): %d integer dot instructions, %d integer "
          "convolutions, %d s8-typed tensor refs"
          % (out_path, s8_dots, s8_convs, s8_tensors))
    if s8_convs == 0 and s8_dots > 0:
        print("=> quantized convs lowered to MXU int8 matmuls "
              "(zero integer convolutions survive)")
    elif s8_convs == 0:
        print("=> no integer dot/conv instructions matched — census "
              "regexes may not fit this backend's HLO format")
    else:
        print("=> %d integer convolutions present — check INT8_CONV_IMPL "
              "dispatch" % s8_convs)
    int8_mod.INT8_CONV_IMPL = prev_impl


if __name__ == "__main__":
    sys.exit(main())
