"""Execution layer: invoke installed executables and apply the fixed
X-RDMA action protocol their results encode.

ABI — how the runtime and injected code meet
--------------------------------------------
The paper's ifunc entry is ``main(payload, payload_size, target_ptr)`` and
may call UCX itself (via remote dynamic linking) to recursively re-inject
itself.  An XLA executable cannot call back into the transport mid-flight,
so the TPU-idiomatic rendering keeps the *decision logic in the shipped
code* and leaves only a fixed, function-agnostic action protocol in the
runtime (the moral equivalent of the UCX API the paper's ifuncs link
against):

* ``update`` ABI — ``entry(payload, region) -> new_region``.  The result
  becomes the named memory region's value, kept on the device (TSI's
  counter); the region is donated, so a small write is made in place.
* ``xrdma`` ABI — ``entry(payload, *linked_deps) -> i64[ACTION_WIDTH]``
  action vector::

      [action, dst, plen, p0 .. p7]

  ``action``: 0 DONE | 1 FORWARD (re-inject *this same ifunc*, code and
  all, to peer ``dst`` with payload ``p[:plen]``) | 2 RETURN (send the
  ifunc named by the ``returns:`` dep to ``dst``) | 3 SPAWN (send the
  ifunc named by the ``spawn:`` dep — "generate new code") | 4 NOP
  (no action; skipped by the runtime) | 5 PUBLISH (re-publish *this same
  ifunc* to peer ``dst`` under a fresh propagation hop header — ``p0`` is
  the hop ttl, ``p[1:plen]`` the published payload; this is how shipped
  code recursively propagates itself, Sec. I).
* ``propagate`` ABI — ``entry(payload, region, *deps) -> (new_region,
  actions)``: one entry both folds into its linked region (like
  ``update``) *and* emits action rows (like ``xrdma``).  Under the
  batched runtime the region fold is the same loop over the real payloads
  as ``update`` — which is exactly what a tree reduction needs: child
  partials fold into the accumulator in one dispatch, and the row whose
  fold completes the subtree emits the upward FORWARD.

  An xrdma entry may instead return an ``(R, W)`` i32 *matrix* of action
  rows; the runtime applies the rows in order.  ``W`` only has to satisfy
  ``W >= 3 + plen`` for every row — rows are self-describing via their
  ``plen`` field, so one rectangular matrix carries ragged payloads.  NOP
  rows are how statically-shaped shipped code emits a *variable* number
  of actions.

  Local recursion — the paper's "ifunc calls itself recursively" when the
  next pointer is local — happens *inside* the shipped code as a
  ``lax.while_loop``: the blob chases until the frontier leaves its shard,
  then emits FORWARD.  One network action per locality break, exactly the
  paper's DAPC behaviour.

The layer is transport-blind: every action that must travel (FORWARD,
RETURN, SPAWN, PUBLISH) is handed to the runtime facade (the ``actions``
collaborator), which owns protocol selection and the wire layer.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any

import numpy as np

from .. import spans
from ..cache import CachedExecutable
from ..frame import ProtocolError
from .. import verify as _verify_codes

if TYPE_CHECKING:  # pragma: no cover - annotations only
    from .codecache import CodeCacheLayer

ACTION_WIDTH = 11  # [action, dst, plen, p0..p7]
A_DONE, A_FORWARD, A_RETURN, A_SPAWN, A_NOP, A_PUBLISH = 0, 1, 2, 3, 4, 5

# core/verify.py mirrors these codes (importing this package there would
# cycle through the pe facade); keep the two in lockstep
assert (A_DONE, A_FORWARD, A_RETURN, A_SPAWN, A_NOP, A_PUBLISH) == (
    _verify_codes.A_DONE, _verify_codes.A_FORWARD, _verify_codes.A_RETURN,
    _verify_codes.A_SPAWN, _verify_codes.A_NOP, _verify_codes.A_PUBLISH,
)


# --------------------------------------------------------- dep-list helpers
def dep_named(exe: CachedExecutable, tag: str) -> str | None:
    """First ``tag:<value>`` entry on the executable's dep list, if any."""
    for d in exe.deps:
        t, _, val = d.partition(":")
        if t == tag:
            return val
    return None


def region_arg_pos(exe: CachedExecutable) -> int:
    """Position of the (single) region among the linked dep arguments."""
    pos = 0
    for d in exe.deps:
        tag, _, _ = d.partition(":")
        if tag == "region":
            return pos
        if tag == "cap":
            pos += 1
    raise AssertionError("update ABI requires a region dep")


class Pending:
    """A dispatch whose outputs are still on their way to the host: what
    :meth:`ExecLayer.complete` needs to finish it."""

    __slots__ = ("exe", "fn", "n", "batched", "host_args", "abi", "region", "out")

    def __init__(self, exe: CachedExecutable, fn, n: int, batched: bool,
                 host_args: list[np.ndarray]) -> None:
        self.exe = exe
        self.fn = fn
        self.n = n  # real payloads (a batched block is padded to its bucket)
        self.batched = batched
        self.host_args = host_args  # the payload or block (and valid mask)
        self.abi = exe.extras.get("abi", "pure")
        self.region = None  # the region an update/propagate result is stored to
        if self.abi in ("update", "propagate"):
            self.region = dep_named(exe, "region")
            assert self.region is not None, f"{self.abi} ABI requires a region dep"
        self.out = None  # the outputs still to reach the host

    def rows(self, out: np.ndarray) -> np.ndarray:
        """The real payloads' outputs of a batched dispatch."""
        return out[: self.n] if self.batched else out


class ExecLayer:
    """Invoke + action application for one PE.

    ``rt`` is the runtime facade (:class:`repro.core.pe.pe.PE`): it links
    dep arguments (regions as their device values, capabilities), keeps
    update-ABI results as their regions' values, collects DONE payloads,
    and carries the travelling actions to the wire.
    """

    def __init__(self, rt, codecache: "CodeCacheLayer", stats, verifier=None) -> None:
        self.rt = rt
        self.codecache = codecache
        self.stats = stats  # the PE's PEStats (shared across layers)
        self.verifier = verifier  # the PE's sandbox ledger (None in bare tests)

    # --- payload/dep decoding ---------------------------------------------
    @staticmethod
    def _pad_ragged(aval, payload: bytes) -> bytes:
        """Zero-extend a ragged payload to the entry's declared aval.

        An xrdma action row's self-describing ``plen`` lets the *send* side
        ship only the meaningful prefix (e.g. a Filter RETURN carrying just
        the survivor rows).  The executable's input shape is static, so an
        entry that declares the ``ragged:`` dep tag opts into receiver-side
        zero-padding — its semantics must not depend on the padded tail
        (the Filter fold scatters by position and drops ``-1`` slots).  A
        payload *longer* than the declared aval is still a protocol error.
        """
        want = int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
        if len(payload) > want:
            raise ProtocolError(
                f"ragged payload of {len(payload)} B exceeds declared {want} B"
            )
        if len(payload) < want:
            payload = bytes(payload) + b"\0" * (want - len(payload))
        return payload

    @staticmethod
    def decode_payload(exe: CachedExecutable, payload: bytes) -> np.ndarray:
        with spans.span("pe/decode") if spans.enabled else spans.NULL:
            aval = exe.in_avals[0]
            if dep_named(exe, "ragged") is not None:
                payload = ExecLayer._pad_ragged(aval, payload)
            arr = np.frombuffer(payload, dtype=aval.dtype)
            return arr.reshape(aval.shape)

    @staticmethod
    def decode_payload_block(
        exe: CachedExecutable, pays: list[bytes], bucket: int
    ) -> np.ndarray:
        """Decode N same-type payloads into a ``(bucket, ...)`` block.

        Padding rows repeat the last real payload: a real payload is known
        to terminate (e.g. a Chaser's ``while_loop`` bound), so edge-repeat
        padding can never hang where zero-padding might; padded outputs are
        simply discarded.
        """
        with spans.span("pe/decode") if spans.enabled else spans.NULL:
            aval = exe.in_avals[0]
            if dep_named(exe, "ragged") is not None:
                pays = [ExecLayer._pad_ragged(aval, p) for p in pays]
            arr = np.frombuffer(b"".join(pays), dtype=aval.dtype)
            arr = arr.reshape((len(pays), *aval.shape))
            if bucket > len(pays):
                arr = np.concatenate(
                    [arr, np.repeat(arr[-1:], bucket - len(pays), axis=0)]
                )
            return arr

    def _dep_args(self, exe: CachedExecutable) -> list[Any]:
        args: list[Any] = []
        for d in exe.deps:
            tag, _, val = d.partition(":")
            if tag == "region":
                args.append(self.rt.region_device(val))
            elif tag == "cap":
                args.append(self.rt.caps[val])
        return args

    # --- invoke -------------------------------------------------------------
    def _dispatch(self, fn, *args):
        """Call a compiled executable; its host (numpy) arguments cross to
        the device with the call."""
        nbytes = sum(a.nbytes for a in args if isinstance(a, np.ndarray))
        self.stats.h2d_bytes += nbytes
        with spans.span("pe/dispatch", bytes=nbytes) if spans.enabled else spans.NULL:
            return fn(*args)

    def _host(self, out) -> np.ndarray:
        """A dispatch's output on the host: waits for the device, then
        copies."""
        nbytes = out.nbytes
        self.stats.d2h_bytes += nbytes
        with spans.span("pe/sync", bytes=nbytes) if spans.enabled else spans.NULL:
            return np.asarray(out)

    def _launch(self, p: "Pending") -> None:
        """Dispatch ``p`` on the regions as they stand now and start copying
        its outputs to the host without waiting for them.  An update or
        propagate result becomes its region's device value here, so a
        dispatch launched after this one reads it, and nothing is stale when
        this one completes."""
        args = self._dep_args(p.exe)
        if p.region is not None and p.batched:  # the fold takes the region first
            rpos = region_arg_pos(p.exe)
            args = [args[rpos], *(a for i, a in enumerate(args) if i != rpos)]
        out = self._dispatch(p.fn, *p.host_args, *args)
        if p.region is not None:  # update: new_region; propagate: (new_region, actions)
            new_region, out = (out, None) if p.abi == "update" else out
            self.rt.write_region(p.region, new_region)
        if out is not None:
            for leaf in out if isinstance(out, (tuple, list)) else (out,):
                leaf.copy_to_host_async()
        p.out = out

    def _begin_one(self, exe: CachedExecutable, payload: bytes) -> "Pending":
        """Decode and dispatch one payload; :meth:`complete` finishes it."""
        ver = self.verifier
        if ver is not None and ver.config.enabled:
            # retire-time quota charge, before the dispatch: code over its
            # payload/invoke budget is refused + quarantined, never run
            ver.charge_invoke(exe, [len(payload)])
        self.stats.invokes += 1
        self.stats.invoked_payloads += 1
        with spans.span("pe/exec", n=1, bucket=1) if spans.enabled else spans.NULL:
            p = Pending(exe, exe.fn, 1, False, [self.decode_payload(exe, payload)])
            self._launch(p)
        return p

    def invoke_batch(self, exe: CachedExecutable, pays: list[bytes]) -> "Pending":
        """Decode N same-ifunc payloads into one block and dispatch it: one
        XLA dispatch retires them all once :meth:`complete` has run."""
        if len(pays) == 1:  # the per-message executable is already compiled
            return self._begin_one(exe, pays[0])
        ver = self.verifier
        if ver is not None and ver.config.enabled:
            ver.charge_invoke(exe, [len(p) for p in pays])
        n = len(pays)
        bucket = self.codecache.bucket(n)
        with spans.span("pe/exec", n=n, bucket=bucket) if spans.enabled else spans.NULL:
            block = self.decode_payload_block(exe, pays, bucket)
            fn = self.codecache.batched_executable(exe, bucket)
            self.stats.invokes += 1
            self.stats.batched_invokes += 1
            self.stats.invoked_payloads += n
            host_args = [block]
            if exe.extras.get("abi", "pure") in ("update", "propagate"):
                host_args.append(np.arange(bucket) < n)  # padded rows fold nothing
            p = Pending(exe, fn, n, True, host_args)
            self._launch(p)
        return p

    def complete(self, p: "Pending") -> None:
        """Wait for a dispatch's outputs and apply them: store the region,
        apply the action rows in payload order, or collect the results."""
        with spans.span("pe/exec", n=p.n) if spans.enabled else spans.NULL:
            self._complete(p)

    def _complete(self, p: "Pending") -> None:
        exe, abi = p.exe, p.abi
        if p.region is not None:
            self.rt.sync_watched(p.region)
        if abi in ("xrdma", "propagate"):
            # a propagate fold's padded rows emitted NOPs; applying the real
            # rows in payload order preserves the sequential semantics (the
            # row that completes a fold emits the action)
            self.apply_actions(exe, p.rows(self._host(p.out)))
        elif abi == "pure":
            out = self._host(p.out)
            if p.batched:
                self.rt.completed.extend(out[: p.n])
            else:
                self.rt.completed.append(out)

    def invoke(self, exe: CachedExecutable, payload: bytes) -> None:
        """Retire one payload: dispatch it and wait for it."""
        self.complete(self._begin_one(exe, payload))

    # --- action application ---------------------------------------------------
    def apply_actions(self, exe: CachedExecutable, out: np.ndarray) -> None:
        """Apply what an xrdma entry returned: one action vector, or an
        (R, W) matrix of action rows applied in order (see module
        docstring); a batched dispatch's (N, ...) stack of either is applied
        payload by payload, in order."""
        rows = out.reshape(-1, out.shape[-1])
        with spans.span("pe/actions", rows=len(rows)) if spans.enabled else spans.NULL:
            for row in rows:
                self.apply_action(exe, row)

    def apply_action(self, exe: CachedExecutable, action: np.ndarray) -> None:
        """The fixed X-RDMA action protocol (see module docstring)."""
        code = int(action[0])
        dst_idx = int(action[1])
        plen = int(action[2])
        pay = np.ascontiguousarray(action[3 : 3 + plen])
        if code == A_NOP:
            return
        ver = self.verifier
        if ver is not None and ver.config.enabled:
            # capability-stamp action whitelist + cumulative action/fan-out
            # quotas; a refused row quarantines the digest before dispatch
            ver.charge_action(exe, code)
        if code == A_DONE:
            self.rt.completed.append(pay)
            return
        dst = self.rt.peers[dst_idx]
        if code == A_FORWARD:
            self.stats.forwards += 1
            self.rt.forward_ifunc(dst, exe, pay)
        elif code == A_RETURN:
            self.stats.returns += 1
            target = dep_named(exe, "returns")
            assert target is not None, "RETURN requires a returns: dep"
            self.rt.return_payload(dst, target, pay)
        elif code == A_SPAWN:
            self.stats.spawns += 1
            target = dep_named(exe, "spawn")
            assert target is not None, "SPAWN requires a spawn: dep"
            self.rt.send_ifunc(dst, target, pay)
        elif code == A_PUBLISH:
            # shipped code re-publishing *itself*: p0 is the hop budget it
            # grants, the rest travels as the published payload — the
            # paper's "recursively propagate itself" emitted by the code,
            # not the runtime
            self.rt.publish_self(dst, exe, pay)
        else:
            raise ProtocolError(f"bad action code {code}")
