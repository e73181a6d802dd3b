"""Public wrapper for the address-decode kernel."""
from __future__ import annotations

from repro.core.addrmap import DecodedAddr
from repro.kernels.addr_decode.kernel import decode_packed, unpack


def decode_skylake(lines, *, interpret: bool = False) -> DecodedAddr:
    """(N,) uint32 cache-line indices -> DecodedAddr via the kernel."""
    ch, rank, bank, row, col = unpack(decode_packed(lines,
                                                    interpret=interpret))
    return DecodedAddr(ch, rank, bank, row, col)


__all__ = ["decode_skylake", "decode_packed", "unpack"]
