"""Frozen deployment artifacts: the Relay/TVM-style IR boundary.

``HybridBlock.export`` historically wrote only the legacy deploy pair
(``path-symbol.json`` + ``path-{epoch:04d}.params``).  For serving, the
same call now also freezes an **artifact manifest**
(``path-artifact.json``): the input signatures (avals), the AMP epoch
and parameter dtype the trace was taken under, and the lowered
**StableHLO** text per signature — parameters ride as arguments, not
constants, so the IR is architecture-sized, not weight-sized.  The
manifest is the contract between export time and serve time: a server
AOT-compiles every manifest signature at startup and then never traces
again (the zero-fresh-trace guarantee the PR 3 compile tracer audits).

``load_artifact`` is the reverse direction: it reconstructs the block
from the symbol + params files via ``SymbolBlock.imports``, hybridizes
it, and (by default) warms every manifest signature so first-request
latency pays no trace.  Round trip is exact: the loaded block produces
identical outputs to the live exporting block (tests pin this for both
formats).
"""
from __future__ import annotations

import json
import os

import numpy as _np

from ..base import MXNetError

__all__ = ["export_artifact", "load_artifact", "write_manifest",
           "manifest_path", "LoadedArtifact"]

MANIFEST_FORMAT = "mxtpu-serving-artifact"
MANIFEST_VERSION = 1


def manifest_path(path):
    return path + "-artifact.json"


def _sig_entry(inputs):
    out = []
    for a in inputs:
        out.append({"shape": [int(s) for s in a.shape],
                    "dtype": str(_np.dtype(a.dtype))})
    return out


def _input_avals(sig):
    import jax

    return [jax.ShapeDtypeStruct(tuple(e["shape"]), _np.dtype(e["dtype"]))
            for e in sig["inputs"]]


def _lower_stablehlo(block, sig_avals):
    """Lower the block's pure functional form at one signature to
    StableHLO text.  Parameters and the RNG key are arguments (the IR
    freezes the *computation*, weights live in the params file)."""
    import jax

    from ..parallel.functional import functionalize

    apply_fn, params = functionalize(block, train_mode=False)
    param_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for k, v in params.items()}
    key_aval = jax.ShapeDtypeStruct((2,), _np.uint32)
    lowered = jax.jit(apply_fn).lower(param_avals, key_aval, *sig_avals)
    return lowered.as_text(dialect="stablehlo")


def write_manifest(block, path, epoch=0, signatures=None, include_ir=True):
    """Write ``path-artifact.json`` for an exported block.

    ``signatures``: list of input tuples (arrays or ShapeDtypeStructs);
    defaults to the block's last traced signature.  Lowering failures
    are recorded per signature (``lower_error``) instead of failing the
    export — the symbol+params round trip stays intact either way."""
    import jax

    sigs = signatures if signatures is not None else \
        [getattr(block, "_last_input_shapes", None)]
    if not sigs or sigs[0] is None:
        raise MXNetError("write_manifest needs at least one input "
                         "signature (run a forward or pass signatures=)")
    from ..ndarray.ndarray import _AMP

    n_inputs = len(sigs[0])
    input_names = ["data"] if n_inputs == 1 else \
        [f"data{i}" for i in range(n_inputs)]
    entries = []
    for sig in sigs:
        entry = {"inputs": _sig_entry(sig)}
        if include_ir:
            try:
                avals = [jax.ShapeDtypeStruct(tuple(a.shape),
                                              _np.dtype(a.dtype))
                         for a in sig]
                entry["stablehlo"] = _lower_stablehlo(block, avals)
            except Exception as e:   # IR is advisory; round trip is not
                entry["lower_error"] = repr(e)[:500]
        entries.append(entry)
    params = sorted(block._collect_params_with_prefix())
    dtypes = sorted({str(p.data().dtype) for p in
                     block._collect_params_with_prefix().values()})
    manifest = {
        "format": MANIFEST_FORMAT,
        "version": MANIFEST_VERSION,
        "symbol": os.path.basename(path) + "-symbol.json",
        "params": os.path.basename(path) + f"-{epoch:04d}.params",
        "epoch": int(epoch),
        "input_names": input_names,
        "signatures": entries,
        "amp_epoch": _AMP["epoch"] if _AMP["on"] else None,
        "param_dtypes": dtypes,
        "num_params": len(params),
    }
    with open(manifest_path(path), "w") as f:
        json.dump(manifest, f, indent=1)
    return manifest


def export_artifact(block, path, epoch=0, signatures=None,
                    include_ir=True):
    """Export a hybridized block as a frozen artifact: the legacy deploy
    pair (via ``HybridBlock.export``) plus the manifest covering every
    signature in ``signatures`` (default: the last traced one).  Returns
    the manifest dict."""
    example = signatures[0] if signatures else ()
    # manifest=False: export would lower signature 0 for a one-entry
    # manifest we immediately replace — skip the duplicate work
    block.export(path, epoch, *example, manifest=False)
    return write_manifest(block, path, epoch=epoch, signatures=signatures,
                          include_ir=include_ir)


class LoadedArtifact:
    """A reconstructed frozen block plus its AOT executable table.

    ``block`` is the ``SymbolBlock`` rebuilt from symbol + params (kept
    for training-time escape hatches: autograd, fine-tuning).  Serving
    calls do NOT go through it — :meth:`warmup` lowers the evaluated
    graph to one ``jax.jit`` executable **per manifest signature**
    (keyed with the PR 1 ``dispatch_cache.signature_key`` discipline,
    compile events recorded under kind ``serving``), and ``__call__``
    dispatches to the compiled table.  A call at a non-manifest
    signature still works but compiles with cause ``steady_state_miss``
    — the tracer makes the contract violation visible instead of
    silently retracing."""

    def __init__(self, block, manifest, path, plan=None):
        self.block = block
        self.manifest = manifest
        self.path = path
        self.warmed = 0
        self._exec: dict = {}
        # rng key rides as a (fixed) argument: inference-mode graphs
        # draw nothing, and freezing the aval keeps signatures stable
        self._zero_key = _np.zeros(2, dtype=_np.uint32)
        names = block._input_names + block._sym_param_names
        self._param_vals = [block.params.get(n).data()._get()
                            for n in block._sym_param_names]
        # planner-sharded AOT (tensor-parallel serving): place the
        # frozen params per the plan once; every signature then compiles
        # against the sharded avals (zero-fresh-trace contract intact)
        self._plan = plan
        self._rep_sharding = None
        if plan is not None:
            import jax

            mesh = plan.build_mesh()
            self._rep_sharding = plan.replicated(mesh)
            self._param_vals = [
                jax.device_put(v, plan.sharding(n, mesh))
                for n, v in zip(block._sym_param_names,
                                self._param_vals)]
        heads = block._sym._heads

        from ..symbol.symbol import evaluate

        def pure(key_val, *vals):
            feed = dict(zip(names, vals))
            outs, _ = evaluate(heads, feed, rng_key=key_val,
                               training=False, collect_state=False)
            return tuple(outs) if len(outs) != 1 else outs[0]

        self._pure = pure

    def signatures(self):
        return [_input_avals(s) for s in self.manifest["signatures"]]

    def _sig_key(self, avals):
        from ..ndarray import dispatch_cache as _dc

        return _dc.signature_key(f"serving:artifact:{self.path}", avals)

    def _aot_compile_signature(self, avals, cause):
        import jax
        import time

        t0 = time.perf_counter()
        key = self._sig_key(avals)
        if key in self._exec:
            return self._exec[key]
        rep = self._rep_sharding
        key_aval = jax.ShapeDtypeStruct((2,), _np.uint32, sharding=rep) \
            if rep is not None else jax.ShapeDtypeStruct((2,), _np.uint32)
        p_avals = [jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=getattr(v, "sharding",
                                                         None))
                   if rep is not None else
                   jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for v in self._param_vals]
        in_avals = [jax.ShapeDtypeStruct(tuple(a.shape),
                                         _np.dtype(a.dtype),
                                         sharding=rep)
                    if rep is not None else
                    jax.ShapeDtypeStruct(tuple(a.shape),
                                         _np.dtype(a.dtype))
                    for a in avals]
        compiled = jax.jit(self._pure).lower(
            key_aval, *in_avals, *p_avals).compile()
        self._exec[key] = compiled
        from .. import telemetry as _telemetry

        _telemetry.compile_event(
            "serving", f"artifact:{os.path.basename(self.path)}",
            time.perf_counter() - t0, cause)
        self.warmed += 1
        return compiled

    def warmup(self):
        """AOT-compile every manifest signature; returns how many fresh
        executables this built."""
        before = self.warmed
        for avals in self.signatures():
            self._aot_compile_signature(avals, "aot_warmup")
        return self.warmed - before

    def __call__(self, *args):
        from ..context import current_context
        from ..ndarray.ndarray import NDArray

        vals = [a._get() if isinstance(a, NDArray) else a for a in args]
        key = self._sig_key(vals)
        compiled = self._exec.get(key)
        if compiled is None:
            compiled = self._aot_compile_signature(vals,
                                                   "steady_state_miss")
        if self._rep_sharding is not None:
            # the sharded executable needs every operand on the plan's
            # mesh; committed single-device NDArrays do not auto-reshard
            import jax

            vals = [jax.device_put(v, self._rep_sharding) for v in vals]
        out = compiled(self._zero_key, *vals, *self._param_vals)
        ctx = args[0].context if args and isinstance(args[0], NDArray) \
            else current_context()
        if isinstance(out, tuple):
            return tuple(NDArray._from_jax(v, ctx) for v in out)
        return NDArray._from_jax(out, ctx)


def load_artifact(path, ctx=None, warm=True, plan=None):
    """Load an exported artifact back: manifest + symbol + params ->
    hybridized SymbolBlock, AOT-warmed across the manifest signatures
    (``warm=False`` skips the warmup).  Outputs are identical to the
    exporting block's.  ``plan``: a
    :class:`~mxnet_tpu.parallel.planner.ShardingPlan` — params are
    placed per the plan's PartitionSpecs and every signature
    AOT-compiles sharded (tensor-parallel serving)."""
    mpath = manifest_path(path)
    if not os.path.exists(mpath):
        raise MXNetError(
            f"no artifact manifest at {mpath} — re-export with this "
            "build (legacy -symbol.json exports predate the manifest)")
    with open(mpath) as f:
        manifest = json.load(f)
    if manifest.get("format") != MANIFEST_FORMAT:
        raise MXNetError(f"{mpath}: not a {MANIFEST_FORMAT} manifest")
    base = os.path.dirname(path)
    sym_file = os.path.join(base, manifest["symbol"])
    params_file = os.path.join(base, manifest["params"])
    from ..gluon.block import SymbolBlock

    block = SymbolBlock.imports(sym_file, manifest["input_names"],
                                params_file, ctx)
    block.hybridize()
    art = LoadedArtifact(block, manifest, path, plan=plan)
    if warm:
        art.warmup()
    return art
