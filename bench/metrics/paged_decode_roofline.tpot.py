"""Roofline share (%) of paged_flash_decode; see readers.decode_roofline."""
from bench.readers import decode_roofline


def read(run):
    return decode_roofline(run)
