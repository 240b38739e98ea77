"""Bytes the ``delta_mask`` changed-block scan moves through HBM.

For a tile-aligned prefix of ``scanned`` bytes in blocks of ``block``
bytes, the kernel reads both encodings once (2 x scanned) and writes one
lane-dense row of 128 int32 flags per block. It compares words and does
nothing else, so HBM bandwidth bounds it.
"""

FLAG_ROW_BYTES = 128 * 4


def bytes_moved(scanned: int, block: int) -> int:
    return 2 * scanned + scanned // block * FLAG_ROW_BYTES
