"""jax-hygiene rule: keep the dispatch hot path async, bucketed, guarded.

Three checks over the TPU dispatch tier — each guards one of the
batching wins behind the throughput headline:

- **host-sync** (interprocedural, via ``callgraph.Analyzer``): a
  device→host synchronization point — ``.item()``, ``device_get``,
  ``np.asarray`` readback, ``block_until_ready``, ``float()`` of a
  computed value — reachable from a hot flush path
  (``*BatchVerifier._verify_pending``, the one dispatch function
  ``tpu/dispatch.py device_verify``, the sidecar
  ``Coalescer._dispatch``). Each flush needs exactly ONE
  deliberate readback of the verdict mask; those sites are baselined
  with that justification, and anything else stalls the pipeline.
- **bucket-bypass** (per-file): a call to a ``@jax.jit``-compiled
  kernel from a function that never references the shape quantizer
  (``tpu/dispatch.py padded_lanes``) — raw batch sizes mean one fresh
  multi-second XLA compile per odd size (a recompile storm).
- **unguarded-dispatch**: a call site of the dispatch function
  (``device_verify``) outside ``tmtpu/tpu/`` whose enclosing function shows no
  breaker/fault discipline (no ``breaker``/``allow``/``guard``/
  ``_dispatch`` wrapper, no fault-injection site) — a device failure
  there escapes the `crypto.*` breaker state machine and has no chaos
  coverage.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tmtpu.analysis.callgraph import Analyzer
from tmtpu.analysis.findings import Finding
from tmtpu.analysis.index import RepoIndex
from tmtpu.analysis.registry import rule

# the one function production code reaches the device through, and the
# one shape quantizer (tmtpu/tpu/dispatch.py)
DISPATCH_FNS = {"device_verify"}
QUANTIZER_TOKENS = {"padded_lanes"}
# hot flush entry points: (class-name-or-None, method/function name)
HOT_SEEDS: Tuple[Tuple[Optional[str], str], ...] = (
    (None, "_verify_pending"),           # every *BatchVerifier flush
    ("Coalescer", "_dispatch"),          # sidecar batching loop
) + tuple((None, fn) for fn in sorted(DISPATCH_FNS))
# markers only count inside the dispatch tier — a float() in some cold
# config helper reached through a deep chain is noise, not a stall
HOT_RELS = ("tmtpu/crypto/", "tmtpu/tpu/", "tmtpu/sidecar/")

GUARD_TOKENS = {"breaker", "allow", "guard", "fire", "_dispatch",
                "note_failure", "with_fallback"}


# ------------------------------------------------------------- host-sync

def _sync_marker(node: ast.AST) -> Optional[str]:
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "item" and not node.args:
            return "host-sync:item"
        if f.attr == "block_until_ready":
            return "host-sync:block_until_ready"
        if f.attr == "device_get":
            return "host-sync:device_get"
        if f.attr == "asarray" and isinstance(f.value, ast.Name) and \
                f.value.id in ("np", "numpy"):
            return "host-sync:np.asarray"
    elif isinstance(f, ast.Name):
        if f.id == "block_until_ready":
            return "host-sync:block_until_ready"
        if f.id == "device_get":
            return "host-sync:device_get"
        if f.id == "float" and node.args and \
                isinstance(node.args[0], (ast.Subscript, ast.Call)):
            # float(arr[0]) / float(jnp.sum(...)) force a device fence;
            # float(name)/float(const) is host arithmetic and exempt
            return "host-sync:float"
    return None


def _check_host_sync(index: RepoIndex) -> List[Finding]:
    an = Analyzer(index, marker_fn=_sync_marker)
    findings, seen = [], set()
    entries = []
    for cls_name, meth in HOT_SEEDS:
        if cls_name is None and meth in DISPATCH_FNS:
            for rel, fn in an._functions_by_name.get(meth, []):
                entries.append((None, fn, rel, meth))
        else:
            for cls in an._methods_by_name.get(meth, []):
                if cls_name is not None and cls.name != cls_name:
                    continue
                entries.append((cls, cls.methods[meth], cls.rel, meth))
    for cls, fn, rel, meth in entries:
        entry = f"{cls.name}.{meth}" if cls is not None else meth
        for ev in an.events(cls, fn=fn, rel=rel):
            if ev.kind != "marker" or \
                    not ev.label.startswith("host-sync:"):
                continue
            if not ev.rel.startswith(HOT_RELS):
                continue
            key = f"jax-hygiene::{ev.label}::{entry}::{ev.rel}" \
                  f"::{ev.chain[-1]}"
            if key in seen:
                continue
            seen.add(key)
            findings.append(Finding(
                "jax-hygiene", ev.rel,
                f"{ev.label.split(':', 1)[1]} on the hot flush path "
                f"{entry}: {ev.rel}:{ev.line} via {ev.via()} — each "
                f"flush should sync the device exactly once, on the "
                f"verdict mask",
                line=ev.line, key=key))
    return findings


# --------------------------------------------------------- bucket-bypass

def _is_jit_decorated(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        node = dec.func if isinstance(dec, ast.Call) else dec
        name = node.attr if isinstance(node, ast.Attribute) else \
            node.id if isinstance(node, ast.Name) else ""
        if name == "jit":
            return True
        if isinstance(dec, ast.Call):          # partial(jax.jit, ...)
            for arg in dec.args:
                n = arg.attr if isinstance(arg, ast.Attribute) else \
                    arg.id if isinstance(arg, ast.Name) else ""
                if n == "jit":
                    return True
    return False


def _fn_tokens(fn: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name):
            out.add(node.id)
        elif isinstance(node, ast.Attribute):
            out.add(node.attr)
    return out


def _top_level_functions(tree: ast.Module):
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node.name, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield f"{node.name}.{sub.name}", sub


def _check_bucket_bypass(index: RepoIndex) -> List[Finding]:
    findings = []
    for fi in index.files("tmtpu"):
        if fi.tree is None:
            continue
        jit_fns = {name for name, fn in _top_level_functions(fi.tree)
                   if _is_jit_decorated(fn)}
        if not jit_fns:
            continue
        for qual, fn in _top_level_functions(fi.tree):
            if fn.name in jit_fns:
                continue                      # jit fns may chain to each other
            called = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name) and \
                        node.func.id in jit_fns:
                    called.add((node.func.id, node.lineno))
            if not called:
                continue
            if _fn_tokens(fn) & QUANTIZER_TOKENS:
                continue
            for callee, line in sorted(called):
                findings.append(Finding(
                    "jax-hygiene", fi.rel,
                    f"{qual} dispatches jit kernel {callee}() without "
                    f"quantizing lane shapes through padded_lanes — "
                    f"every odd batch size triggers a fresh XLA compile",
                    line=line,
                    key=f"jax-hygiene::bucket-bypass::{fi.rel}::{qual}"
                        f"::{callee}"))
    return findings


# ----------------------------------------------------- unguarded-dispatch

def _check_unguarded_dispatch(index: RepoIndex) -> List[Finding]:
    findings = []
    for fi in index.files("tmtpu"):
        if fi.tree is None or fi.rel.startswith("tmtpu/tpu/"):
            continue                          # definitions live there
        for qual, fn in _top_level_functions(fi.tree):
            sites = []
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                name = f.attr if isinstance(f, ast.Attribute) else \
                    f.id if isinstance(f, ast.Name) else ""
                if name in DISPATCH_FNS:
                    sites.append((name, node.lineno))
            if not sites:
                continue
            if _fn_tokens(fn) & GUARD_TOKENS:
                continue
            for name, line in sorted(sites):
                findings.append(Finding(
                    "jax-hygiene", fi.rel,
                    f"{qual} calls {name}() outside any crypto.* breaker "
                    f"or fault site — a device failure here escapes the "
                    f"breaker state machine",
                    line=line,
                    key=f"jax-hygiene::unguarded-dispatch::{fi.rel}"
                        f"::{qual}::{name}"))
    return findings


@rule("jax-hygiene",
      doc="no stray host-sync on hot flush paths, no jit dispatch "
          "bypassing the padded_lanes shape quantizer, no device_verify "
          "call outside a crypto.* breaker or fault site",
      triggers=("tmtpu/crypto", "tmtpu/tpu", "tmtpu/sidecar", "tmtpu"))
def check(index: RepoIndex) -> List[Finding]:
    findings: List[Finding] = []
    findings += _check_host_sync(index)
    findings += _check_bucket_bypass(index)
    findings += _check_unguarded_dispatch(index)
    return findings
