"""Code-cache layer: install arriving code, validate digests, and build
the batched (bucketed) executables the batched runtime dispatches.

Target side of Sec. III-C/D: extract the triple's slice from a fat-bitcode
archive -> (ORC-)JIT -> digest cache, with the name registry deciding
whether a truncated (digest-only) frame is acceptable and the digest
deciding whether a name's code is *current*.  The batched renderings —
``lax.map`` for value ABIs, the fold over the real payloads for
update/propagate ABIs — are cached per (digest, power-of-two bucket) in
the same :class:`repro.core.cache.TargetCodeCache`.  Every executable is
compiled for the PE's own device.
"""

from __future__ import annotations

import hashlib
import re
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import SingleDeviceSharding

from .. import spans
from ..bitcode import FatBitcode
from ..cache import CachedExecutable, TargetCodeCache
from ..frame import Frame, FrameKind, ProtocolError
from .exec import A_NOP, region_arg_pos


class ISAMismatch(RuntimeError):
    """Binary ifunc landed on a PE whose triple it was not compiled for."""


def named(fn, ifunc: str):
    """``fn`` renamed ``<its name>_<ifunc>`` (sanitised), so its executable
    reads ``jit_mapped_gatherer`` in HLO and in the device trace, not
    ``jit_mapped`` whatever the ifunc."""
    fn.__name__ = f"{fn.__name__}_{re.sub(r'[^0-9A-Za-z_]', '_', ifunc)}"
    return fn


class CodeCacheLayer:
    """Install/resolve/batch-compile for one PE's target code cache."""

    def __init__(
        self,
        name: str,
        triple: str,
        cache: TargetCodeCache,
        stats,
        device: jax.Device,
        verifier=None,
    ) -> None:
        self.name = name
        self.triple = triple
        self.cache = cache
        self.stats = stats  # the PE's PEStats (shared across layers)
        self.sharding = SingleDeviceSharding(device)  # where code compiles for
        self.verifier = verifier  # the PE's Verifier (None in bare tests)

    def _on_device(self, shape, dtype) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self.sharding)

    def _gate(self, name, digest_hex, deps, exported, admitted_ttl=None) -> None:
        """Run the install-time verifier over one code-cache ingress.  A
        stamped digest is a dict hit (the warm path the benchmark pins at
        zero cost); a quarantined or failing one raises SandboxViolation
        before the code becomes resolvable."""
        ver = self.verifier
        if ver is not None and ver.config.enabled:
            ver.admit(name, digest_hex, deps, exported, admitted_ttl)

    # --- install ----------------------------------------------------------
    def install(
        self, frame: Frame, admitted_ttl: int | None = None
    ) -> CachedExecutable:
        """Extract slice -> verify -> (ORC-)JIT -> digest cache (Sec.
        III-C/D).  ``admitted_ttl`` is the admitting PUBLISH hop's
        remaining budget, clamped into the capability stamp's re-mint
        ceiling.

        A digest hit skips compilation entirely (ORC-JIT's internal symbol
        cache, which the paper observed makes re-JIT of already-seen code
        free) — only the name registration is new."""
        hit = self.cache.lookup_digest(frame.digest.hex())
        if hit is not None:
            self._gate(
                frame.name, hit.digest, frame.deps or hit.deps,
                hit.extras.get("exported"), admitted_ttl,
            )
            exe = CachedExecutable(
                name=frame.name,
                digest=hit.digest,
                fn=hit.fn,
                in_avals=hit.in_avals,
                deps=frame.deps or hit.deps,
                kind=int(frame.kind),
                extras=dict(hit.extras),
            )
            self.cache.install(exe, jit_ms=0.0)
            self.stats.ifunc_installs += 1
            return exe

        fat = FatBitcode.from_bytes(frame.code)
        if frame.kind == FrameKind.BINARY:
            # binary code is ISA/uarch-specific: exact triple or bust
            if self.triple not in fat.slices:
                raise ISAMismatch(
                    f"binary ifunc {frame.name!r} built for {fat.triples()} "
                    f"cannot run on {self.triple!r} (Sec. III-B problem; "
                    f"ship bitcode instead)"
                )
            blob = fat.slices[self.triple]
        else:
            blob = fat.extract(self.triple).blob
        exported = jax.export.deserialize(blob)
        # verify between deserialize and compile: a refused slice must not
        # cost this PE an XLA compilation (the compile itself is a resource)
        self._gate(frame.name, frame.digest.hex(), frame.deps, exported, admitted_ttl)
        t0 = time.perf_counter()
        avals = [self._on_device(a.shape, a.dtype) for a in exported.in_avals]

        def call(*args):
            return exported.call(*args)

        abi = "pure"
        for d in frame.deps:
            if d.startswith("abi:"):
                abi = d.split(":", 1)[1]
        # an update's region is donated: its result is written in place
        donate = (1 + region_arg_pos(frame),) if abi in ("update", "propagate") else ()
        with spans.span("pe/compile", bucket=1) if spans.enabled else spans.NULL:
            compiled = jax.jit(
                named(call, frame.name), donate_argnums=donate).lower(*avals).compile()
        jit_ms = (time.perf_counter() - t0) * 1e3
        exe = CachedExecutable(
            name=frame.name,
            digest=frame.digest.hex(),
            fn=compiled,
            in_avals=tuple(exported.in_avals),
            deps=frame.deps,
            kind=int(frame.kind),
            extras={"code": frame.code, "abi": abi, "exported": exported},
        )
        self.cache.install(exe, jit_ms=jit_ms)
        self.stats.ifunc_installs += 1
        self.stats.jit_ms_total += jit_ms
        return exe

    # --- resolve ----------------------------------------------------------
    def resolve_exe(self, buf: bytes, hdr) -> tuple[CachedExecutable, Frame]:
        """Find (or install) the executable a frame refers to; returns it
        with the frame unpacked exactly once (code-carrying frames are
        multi-KB, a second parse is a second copy).

        The name registry decides whether a truncated frame is acceptable;
        the digest decides whether the name's code is *current* — a frame
        carrying new code under a known name (republished ifunc) installs
        and supersedes, it never silently runs the stale executable.
        """
        with spans.span("pe/resolve") if spans.enabled else spans.NULL:
            return self._resolve_exe(buf, hdr)

    def _resolve_exe(self, buf: bytes, hdr) -> tuple[CachedExecutable, Frame]:
        from ..frame import unpack

        has_code = len(buf) >= hdr.full_total and hdr.code_len > 0
        frame = unpack(buf, has_code=has_code)
        if not self.cache.has_name(hdr.name):
            if not has_code:
                raise ProtocolError(
                    f"{self.name}: truncated frame for unregistered ifunc "
                    f"{hdr.name!r} (stale sender cache — was this PE restarted?)"
                )
            return self.install(frame), frame
        exe = self.cache.lookup(hdr.name)
        assert exe is not None
        if exe.digest != hdr.digest.hex():
            if has_code:
                return self.install(frame), frame
            hit = self.cache.lookup_digest(hdr.digest.hex())
            if hit is None:
                raise ProtocolError(
                    f"{self.name}: truncated frame for {hdr.name!r} with "
                    f"unknown code digest (stale sender cache)"
                )
            exe = hit
        # warm-path gate: quarantine refusal or stamp dict hit; a digest
        # never seen by an (enabled-later) verifier is admitted here
        self._gate(exe.name, exe.digest, exe.deps, exe.extras.get("exported"))
        return exe, frame

    def validate_publish_code(self, frame: Frame, hdr) -> None:
        """Poisoned-code gate: a code-carrying publish whose code section
        does not hash to the header digest is refused loudly (and the
        caller must not re-publish it down the tree)."""
        if hashlib.sha256(frame.code).digest() != frame.digest:
            self.stats.refuse("publish_digest")
            raise ProtocolError(
                f"{self.name}: publish of {hdr.name!r} carries code that does "
                f"not match its digest (poisoned code refused, not re-published)"
            )

    def resolve_publish_exe(
        self, hdr, admitted_ttl: int | None = None
    ) -> CachedExecutable:
        """Resolve a digest-only (truncated) publish: the code must already
        be digest-cached here, or the sender's cache belief was stale."""
        exe = self.cache.lookup(hdr.name)
        if exe is None or exe.digest != hdr.digest.hex():
            hit = self.cache.lookup_digest(hdr.digest.hex())
            if hit is None:
                raise ProtocolError(
                    f"{self.name}: digest-only publish for unknown code "
                    f"{hdr.name!r} (stale sender cache — was this PE "
                    f"restarted?)"
                )
            exe = CachedExecutable(
                name=hdr.name,
                digest=hit.digest,
                fn=hit.fn,
                in_avals=hit.in_avals,
                deps=hit.deps,
                kind=int(hdr.kind),
                extras=dict(hit.extras),
            )
            self._gate(
                exe.name, exe.digest, exe.deps,
                exe.extras.get("exported"), admitted_ttl,
            )
            self.cache.install(exe, jit_ms=0.0)
            self.stats.ifunc_installs += 1
        else:
            self._gate(
                exe.name, exe.digest, exe.deps,
                exe.extras.get("exported"), admitted_ttl,
            )
        return exe

    # --- batched executables ----------------------------------------------
    @staticmethod
    def bucket(n: int) -> int:
        """Power-of-two padding bucket: bounds batched recompiles to log2."""
        return 1 << max(0, n - 1).bit_length()

    def batched_executable(self, exe: CachedExecutable, bucket: int):
        """The batched rendering of an installed ifunc, cached per
        (digest, bucket) in the target code cache.

        Value ABIs run ``lax.map`` over the payload block — sequential
        semantics inside ONE fused XLA dispatch, which is the quantity
        being amortized (``jax.vmap`` has no batching rule for
        ``call_exported``).  update-ABI code folds the real payloads into
        the donated region with a loop (exact sequential semantics, one
        dispatch, the region written in place).
        """
        with spans.span("pe/resolve") if spans.enabled else spans.NULL:
            hit = self.cache.lookup_batched(exe.digest, bucket)
        if hit is not None:
            return hit
        exported = exe.extras["exported"]
        call = exported.call
        abi = exe.extras.get("abi", "pure")
        pay_aval = exe.in_avals[0]
        block_aval = self._on_device((bucket, *pay_aval.shape), pay_aval.dtype)
        dep_avals = tuple(self._on_device(a.shape, a.dtype) for a in exe.in_avals[1:])
        t0 = time.perf_counter()
        donate = ()
        if abi in ("update", "propagate"):
            # entry(payload, ..region.., ...) -> new_region (update) or
            # (new_region, actions) (propagate), folded over the real
            # payloads alone — the valid mask is a prefix, so a loop over
            # its first n rows carries the donated region and its work does
            # not scale with the region; a padded propagate row emits NOPs.
            valid_aval = self._on_device((bucket,), jnp.bool_)
            rpos = region_arg_pos(exe)

            def folded(pays, valid, region, *extra):
                def apply(p, r):
                    dep_args = list(extra)
                    dep_args.insert(rpos, r)
                    return call(p, *dep_args)

                n = jnp.sum(valid.astype(jnp.int32))
                if abi == "update":
                    return lax.fori_loop(0, n, lambda i, r: apply(pays[i], r), region)
                acts0 = jax.eval_shape(apply, pays[0], region)[1]
                nops = jnp.zeros((bucket, *acts0.shape), acts0.dtype).at[..., 0].set(A_NOP)

                def step(i, carry):
                    r, acts = carry
                    nr, a = apply(pays[i], r)
                    return nr, lax.dynamic_update_index_in_dim(acts, a, i, 0)

                return lax.fori_loop(0, n, step, (region, nops))

            extra_avals = [a for i, a in enumerate(dep_avals) if i != rpos]
            fn, avals = folded, (block_aval, valid_aval, dep_avals[rpos], *extra_avals)
            donate = (2,)
        else:
            def mapped(pays, *deps):
                return lax.map(lambda p: call(p, *deps), pays)

            fn, avals = mapped, (block_aval, *dep_avals)
        with spans.span("pe/compile", bucket=bucket) if spans.enabled else spans.NULL:
            compiled = jax.jit(named(fn, exe.name), donate_argnums=donate).lower(*avals).compile()
        self.stats.jit_ms_total += (time.perf_counter() - t0) * 1e3
        self.cache.install_batched(exe.digest, bucket, compiled)
        return compiled
