"""Dispatch for the endorsement-MAC kernel: the Pallas kernel when the
caller asks for it (``interpret`` only without a TPU), else the reference."""

from __future__ import annotations

from repro.kernels.sig_mac import kernel, ref


def mac_many(msg, rs, ss, *, use_pallas: bool = False,
             interpret: bool = False):
    """(B, W) messages x (NE,) keys -> (B, NE) tags."""
    if use_pallas:
        return kernel.mac_many(msg, rs, ss, interpret=interpret)
    return ref.mac_many_ref(msg, rs, ss)
