"""Fat-bitcode: multi-target portable code archives.

The paper ships LLVM bitcode compiled for every ISA the ifunc may land on
("fat-bitcode", Fig. 3) so the target can extract the slice matching its own
triple and JIT-optimize it for the local microarchitecture.

The JAX analogue of LLVM bitcode is a ``jax.export`` blob: serialized,
versioned StableHLO that is platform-portable and is re-lowered/optimized by
the *target's* XLA backend at deserialization+jit time (ORC-JIT's role).  A
:class:`FatBitcode` maps target triples (e.g. ``cpu-host``, ``tpu-v5e``) to
export blobs; archives are content-addressed by a sha256 digest, which is what
the caching protocol (frame truncation + target JIT cache) keys on.
"""

from __future__ import annotations

import hashlib
import io
import struct
from dataclasses import dataclass, field
from functools import cached_property
from types import MappingProxyType
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.export

from . import spans
from .frame import CorruptFrame

# Target triples. ``platform`` is what jax.export lowers for and which JAX
# backend runs the slice; the suffix models the micro-architecture the paper
# optimizes for on the target (A64FX SVE vs. Xeon AVX2).  Every slice is
# generated on any machine (cross-lowering, like the paper generating
# AArch64 bitcode on a Xeon); a slice runs only in a process that has a
# device of its platform (see :func:`device_of`).
_TRIPLE_PLATFORM: dict[str, str] = {
    "cpu-host": "cpu",
    "cpu-a64fx": "cpu",
    "cpu-bf2": "cpu",
    "tpu-v5e": "tpu",
}

# ``jax.Device.device_kind`` -> the triple of a PE running on that device
_KIND_TRIPLE: dict[str, str] = {
    "cpu": "cpu-host",
    "TPU v5 lite": "tpu-v5e",
}

DEFAULT_TOOLCHAIN_TARGETS: tuple[str, ...] = ("cpu-host", "tpu-v5e")

_MAGIC = b"FBC1"


def platform_of(triple: str) -> str:
    try:
        return _TRIPLE_PLATFORM[triple]
    except KeyError:
        raise ValueError(f"unknown target triple: {triple!r}") from None


def device_of(triple: str) -> jax.Device:
    """The device a PE of ``triple`` computes on: the first device of the
    triple's platform.  Raises when this process has no such device — a
    ``tpu-v5e`` PE never silently runs on the host CPU."""
    plat = platform_of(triple)
    try:
        return jax.devices(plat)[0]
    except RuntimeError as e:
        raise RuntimeError(
            f"no {plat!r} device in this process for a {triple!r} PE: {e}"
        ) from None


def local_triple() -> str:
    """The triple of the default device this process runs on."""
    kind = jax.devices()[0].device_kind
    try:
        return _KIND_TRIPLE[kind]
    except KeyError:
        raise ValueError(f"no target triple for device kind {kind!r}") from None


@dataclass(frozen=True)
class BitcodeSlice:
    """One target's worth of code: the analogue of a single .bc file."""

    triple: str
    blob: bytes

    @property
    def digest(self) -> str:
        return hashlib.sha256(self.blob).hexdigest()


@dataclass(frozen=True)
class FatBitcode:
    """Archive of per-triple export blobs (paper Fig. 3 BITCODE fields).

    Immutable once built: ``slices`` is a read-only mapping, so the wire
    bytes and their digest are made once per archive, not once per frame
    that carries it."""

    slices: Mapping[str, bytes] = field(default_factory=dict)

    def __post_init__(self) -> None:
        object.__setattr__(self, "slices", MappingProxyType(dict(self.slices)))

    # -- construction -------------------------------------------------------
    @classmethod
    def build(
        cls,
        fn: Callable[..., Any],
        in_avals: Sequence[jax.ShapeDtypeStruct],
        targets: Sequence[str] = DEFAULT_TOOLCHAIN_TARGETS,
        fn_by_platform: Mapping[str, Callable[..., Any]] | None = None,
    ) -> "FatBitcode":
        """Cross-compile ``fn`` for every toolchain target.

        Mirrors "the Three-Chains toolchain will generate bitcode files for
        all the targets supported by the toolchain's Clang compiler".

        ``fn_by_platform`` optionally overrides the entry per *platform*
        (``"cpu"``/``"tpu"``) or per exact *triple* (``"cpu-bf2"``): the
        toolchain analogue of per-ISA intrinsics behind one source — e.g.
        the Gatherer ships a Pallas ``embed_lookup`` body in its TPU slice
        and the masked-take reference everywhere else, and the pushdown
        Filter ships a masked-take body in its DPU (``cpu-bf2``) slice.
        A triple key wins over its platform key (both map to the same
        lowering platform — the BF2's Arm cores are still ``"cpu"`` to
        XLA, but its slice may carry a different body).  Every slice must
        compute the same function; only the lowering differs.  A body that
        fails to lower fails the build.
        """
        slices: dict[str, bytes] = {}
        overrides = dict(fn_by_platform or {})
        for triple in targets:
            plat = platform_of(triple)
            entry = overrides.get(triple, overrides.get(plat, fn))
            exported = jax.export.export(jax.jit(entry), platforms=[plat])(*in_avals)
            slices[triple] = exported.serialize()
        return cls(slices=slices)

    # -- the wire format ----------------------------------------------------
    @cached_property
    def _sealed(self) -> tuple[bytes, bytes]:
        """The wire bytes and their sha256, made on first use (the one
        ``pe/archive`` span of this archive)."""
        with spans.span("pe/archive") if spans.enabled else spans.NULL as sp:
            out = io.BytesIO()
            out.write(_MAGIC)
            out.write(struct.pack("<H", len(self.slices)))
            for triple in sorted(self.slices):
                blob = self.slices[triple]
                t = triple.encode()
                out.write(struct.pack("<HI", len(t), len(blob)))
                out.write(t)
                out.write(blob)
            data = out.getvalue()
            if sp is not None:
                sp.set(bytes=len(data))
            return data, hashlib.sha256(data).digest()

    def to_bytes(self) -> bytes:
        return self._sealed[0]

    @property
    def sha256(self) -> bytes:
        """The raw sha256 of :meth:`to_bytes`: the archive's content address."""
        return self._sealed[1]

    @classmethod
    def from_bytes(cls, data: bytes) -> "FatBitcode":
        """Parse one archive; anything malformed — truncated slice table,
        undecodable triple, lengths past the end of the buffer — is a loud
        :class:`~repro.core.frame.CorruptFrame`, never a struct/index/
        decode error leaking out of a hostile frame."""
        if data[:4] != _MAGIC:
            raise CorruptFrame("not a fat-bitcode archive")
        if len(data) < 6:
            raise CorruptFrame("corrupt fat-bitcode: truncated slice count")
        (n,) = struct.unpack_from("<H", data, 4)
        off = 6
        slices: dict[str, bytes] = {}
        for _ in range(n):
            if len(data) < off + 6:
                raise CorruptFrame("corrupt fat-bitcode: truncated slice header")
            tlen, blen = struct.unpack_from("<HI", data, off)
            off += 6
            if len(data) < off + tlen + blen:
                raise CorruptFrame("corrupt fat-bitcode: slice exceeds archive")
            try:
                triple = data[off : off + tlen].decode()
            except UnicodeDecodeError as e:
                raise CorruptFrame(
                    f"corrupt fat-bitcode: undecodable triple ({e})"
                ) from None
            off += tlen
            slices[triple] = data[off : off + blen]
            off += blen
        return cls(slices=slices)

    # -- target-side extraction --------------------------------------------
    def extract(self, triple: str | None = None) -> BitcodeSlice:
        """Pick the slice matching the local target triple.

        Falls back to any slice with the same *platform* (µarch variants of
        one ISA share bitcode; ORC-JIT specializes at codegen time).
        """
        triple = triple or local_triple()
        if triple in self.slices:
            return BitcodeSlice(triple, self.slices[triple])
        want = platform_of(triple)
        for t, blob in sorted(self.slices.items()):
            if platform_of(t) == want:
                return BitcodeSlice(t, blob)
        raise LookupError(
            f"fat-bitcode has no slice for {triple!r} (have {sorted(self.slices)})"
        )

    @property
    def digest(self) -> str:
        return self.sha256.hex()

    @property
    def nbytes(self) -> int:
        return len(self.to_bytes())

    def triples(self) -> tuple[str, ...]:
        return tuple(sorted(self.slices))

