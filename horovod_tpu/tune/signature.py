"""Abstract step signatures — the key a pinned ``tuned.json`` is valid for.

A tuned configuration is only meaningful for the program it was tuned on:
the gradient pytree's structure decides the stream-group partition, the
leaf shapes/dtypes decide bucket payloads, and the mesh topology decides
which lowerings exist. The signature captures exactly those inputs and
nothing else (no values, no device ids, no hostnames):

- ``treedef`` — ``str(jax.tree.structure(params))``;
- ``leaves`` — per-leaf ``[shape..., dtype]`` in flatten order;
- ``mesh`` — the mesh axis sizes (``Mesh.shape``) or the interconnect
  model's ``(hop name, size)`` ladder, whichever the caller has.

``signature_hash`` is a SHA-256 prefix over the canonical (sorted-keys)
JSON, so two runs of the tuner on the same program emit byte-identical
keys and a consumer can compare hashes without materializing params.
Works on concrete arrays and ``jax.ShapeDtypeStruct`` avals alike — the
tuner never has to touch a backend to key its output.
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, Optional

SIGNATURE_VERSION = 1


def _mesh_component(mesh: Any = None, model: Any = None) -> Dict:
    if mesh is not None:
        shape = getattr(mesh, "shape", None)
        if shape is not None:
            return {"axes": {str(k): int(v) for k, v in dict(shape).items()}}
        return {"axes": {str(k): int(v) for k, v in dict(mesh).items()}}
    if model is not None:
        return {
            "hops": [[h.name, int(h.size)] for h in model.hops],
        }
    return {}


def step_signature(params: Any, mesh: Any = None,
                   model: Any = None) -> Dict:
    """Signature dict for a params pytree (arrays or avals) on a mesh
    (a ``jax.sharding.Mesh``, an ``{axis: size}`` dict, or None) or an
    interconnect model."""
    import jax

    leaves, treedef = jax.tree.flatten(params)
    sig = {
        "version": SIGNATURE_VERSION,
        "treedef": str(treedef),
        "leaves": [
            [list(int(d) for d in getattr(l, "shape", ())),
             str(getattr(l, "dtype", "?"))]
            for l in leaves
        ],
        "mesh": _mesh_component(mesh, model),
    }
    sig["hash"] = signature_hash(sig)
    return sig


def signature_hash(sig: Dict) -> str:
    """Stable 16-hex-digit key over the signature's canonical JSON (the
    ``hash`` field itself excluded)."""
    body = {k: v for k, v in sig.items() if k != "hash"}
    blob = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def mesh_axes_hash(sig: Optional[Dict]) -> str:
    """16-hex key over ONLY the mesh component of a signature — what
    lets a consumer say WHY a match failed: same program pinned on a
    different mesh (axes hash differs) vs a different program entirely.
    The wire-dtype verdict is a function of the mesh's hop ladder, so a
    tuning pinned on another mesh says nothing about ``quantized``."""
    body = (sig or {}).get("mesh") or {}
    blob = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def params_match(tuned_sig: Optional[Dict], live_sig: Dict) -> bool:
    """Whether only the params half (treedef + leaves) agrees."""
    return signatures_match(tuned_sig, live_sig, require_mesh=False)


def signatures_match(tuned_sig: Optional[Dict], live_sig: Dict,
                     require_mesh: bool = True) -> bool:
    """Whether a pinned signature covers the live program. Hash equality
    is the fast path; ``require_mesh=False`` compares only the params
    component (``DistributedOptimizer`` sees gradients but no mesh, so
    it cannot hold the tuning to the mesh half of the key)."""
    if not tuned_sig:
        return False
    if require_mesh:
        return tuned_sig.get("hash") == live_sig.get("hash")
    a = {"treedef": tuned_sig.get("treedef"),
         "leaves": tuned_sig.get("leaves")}
    b = {"treedef": live_sig.get("treedef"),
         "leaves": live_sig.get("leaves")}
    return a == b
