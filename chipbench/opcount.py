"""Operations and bytes of the kernels the benchmark holds to a roofline,
computed from shapes. Kept with the benchmark so that no later PR can
move the yardstick."""


def aggregate_bytes(n: int, d: int, itemsize: int = 4) -> int:
    """Least bytes a robust aggregate of an (n, d) matrix must move: the
    matrix read once, the (d,) result written once."""
    return n * d * itemsize + d * itemsize
