"""Dispatch for the MVCC validation kernel: the Pallas kernel when the
caller asks for it (``interpret`` only without a TPU), else the reference."""

from __future__ import annotations

from repro.kernels.mvcc_validate import kernel, ref


def validate(read_keys, read_vers, write_keys, current_versions, ok0,
             *, use_pallas: bool = False, interpret: bool = False):
    """Single-block validate: (B,RK,2),(B,RK),(B,WK,2),(B,RK),(B,) -> (B,)."""
    if use_pallas:
        return kernel.validate_blocks(
            read_keys[None], read_vers[None], write_keys[None],
            current_versions[None], ok0[None], interpret=interpret,
        )[0]
    return ref.validate_ref(
        read_keys, read_vers, write_keys, current_versions, ok0
    )
