"""API parity audit: reference python/paddle __all__ lists vs paddle_tpu exports.

Parses the reference source with ast (it is not importable — C++ core), and
imports paddle_tpu for real. Prints missing names per namespace.
"""
from __future__ import annotations

import ast
import importlib
import os
import sys
from pathlib import Path

REF = Path("/root/reference/python/paddle")

# namespace -> (reference file(s) carrying __all__, our module path)
# Namespaces whose reference module exposes no __all__ (round-4 verdict
# item 9: the audit previously printed "NO __all__ FOUND" and checked
# nothing there). Expected names hand-rolled from the reference source:
# python/paddle/callbacks.py:15-21 re-exports exactly these from
# hapi/callbacks.py (whose own __all__ is empty).
HAND_ROLLED = {
    "paddle.callbacks": ["Callback", "ProgBarLogger", "ModelCheckpoint",
                         "VisualDL", "LRScheduler", "EarlyStopping",
                         "ReduceLROnPlateau"],
}

NAMESPACES = {
    "paddle (tensor methods/ops)": (["__init__.py"], "paddle_tpu"),
    "paddle.nn": (["nn/__init__.py"], "paddle_tpu.nn"),
    "paddle.nn.functional": (["nn/functional/__init__.py"], "paddle_tpu.nn.functional"),
    "paddle.nn.initializer": (["nn/initializer/__init__.py"], "paddle_tpu.nn.initializer"),
    "paddle.linalg": (["linalg.py"], "paddle_tpu.linalg"),
    "paddle.fft": (["fft.py"], "paddle_tpu.fft"),
    "paddle.signal": (["signal.py"], "paddle_tpu.signal"),
    "paddle.optimizer": (["optimizer/__init__.py"], "paddle_tpu.optimizer"),
    "paddle.optimizer.lr": (["optimizer/lr.py"], "paddle_tpu.optimizer.lr"),
    "paddle.metric": (["metric/__init__.py"], "paddle_tpu.metric"),
    "paddle.distribution": (["distribution/__init__.py"], "paddle_tpu.distribution"),
    "paddle.distributed": (["distributed/__init__.py"], "paddle_tpu.distributed"),
    "paddle.vision": (["vision/__init__.py"], "paddle_tpu.vision"),
    "paddle.vision.models": (["vision/models/__init__.py"], "paddle_tpu.vision.models"),
    "paddle.vision.datasets": (["vision/datasets/__init__.py"], "paddle_tpu.vision.datasets"),
    "paddle.vision.ops": (["vision/ops.py"], "paddle_tpu.vision.ops"),
    "paddle.vision.transforms": (["vision/transforms/__init__.py"], "paddle_tpu.vision.transforms"),
    "paddle.io": (["io/__init__.py"], "paddle_tpu.io"),
    "paddle.amp": (["amp/__init__.py"], "paddle_tpu.amp"),
    "paddle.jit": (["jit/__init__.py"], "paddle_tpu.jit"),
    "paddle.static": (["static/__init__.py"], "paddle_tpu.static"),
    "paddle.static.nn": (["static/nn/__init__.py"], "paddle_tpu.static.nn"),
    "paddle.sparse": (["sparse/__init__.py"], "paddle_tpu.sparse"),
    "paddle.text": (["text/__init__.py"], "paddle_tpu.text"),
    "paddle.utils": (["utils/__init__.py"], "paddle_tpu.utils"),
    "paddle.incubate": (["incubate/__init__.py"], "paddle_tpu.incubate"),
    "paddle.autograd": (["autograd/__init__.py"], "paddle_tpu.autograd"),
    "paddle.callbacks": (["callbacks.py"], "paddle_tpu.callbacks"),
    "paddle.regularizer": (["regularizer.py"], "paddle_tpu.regularizer"),
    "paddle.profiler": (["profiler/__init__.py"], "paddle_tpu.profiler"),
    "paddle.device": (["device/__init__.py"], "paddle_tpu.framework.device"),
    "paddle.onnx": (["onnx/__init__.py"], "paddle_tpu.onnx"),
}


def ref_all(rel_paths):
    names = []
    for rel in rel_paths:
        p = REF / rel
        if not p.exists():
            continue
        tree = ast.parse(p.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__":
                        try:
                            names += [e for e in ast.literal_eval(node.value)]
                        except Exception:
                            pass
            elif isinstance(node, ast.AugAssign):
                if isinstance(node.target, ast.Name) and node.target.id == "__all__":
                    try:
                        names += [e for e in ast.literal_eval(node.value)]
                    except Exception:
                        pass
    return sorted(set(names))


def main():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    total_missing = 0
    report = []
    for ns, (rels, ours_path) in NAMESPACES.items():
        ref_names = ref_all(rels) or HAND_ROLLED.get(ns, [])
        if not ref_names:
            report.append((ns, None, None, "NO __all__ FOUND"))
            continue
        try:
            ours = importlib.import_module(ours_path)
        except Exception as e:
            report.append((ns, len(ref_names), None, f"IMPORT FAIL: {e}"))
            continue
        missing = [n for n in ref_names if not hasattr(ours, n)]
        total_missing += len(missing)
        report.append((ns, len(ref_names), missing, None))
    for ns, nref, missing, err in report:
        if err:
            print(f"== {ns}: {err}")
            continue
        print(f"== {ns}: {nref - len(missing)}/{nref} present, {len(missing)} missing")
        if missing:
            for i in range(0, len(missing), 8):
                print("   " + ", ".join(missing[i:i + 8]))
    print(f"\nTOTAL MISSING: {total_missing}")


if __name__ == "__main__":
    main()
