"""The bytes a kernel has to move for one call, computed from its shapes.
Kept here so that no later change to the program can move a roofline."""

from __future__ import annotations


def produce_hbm_bytes(payload_bytes: int) -> int:
    """The program that makes a request, where the echo step is a kernel:
    the step reads the request once and writes the response once (the
    checksum rides the same pass), and the yardstick's add of the checksum
    to every element, which cannot fuse into a kernel, does so again."""
    return 4 * payload_bytes


def exchange_bytes_leaving_chip(bytes_per_chip: int, peers: int) -> int:
    """In an N-to-N exchange each chip keeps the row addressed to itself
    and sends the other N-1."""
    return bytes_per_chip // peers * (peers - 1)
