"""Custom Pallas TPU kernels for the tiering hot path (selection, page
moves) and the serving path (tiered attention, flash attention, SSD scan)."""


def pad_up(n: int, m: int) -> int:
    """``n`` rounded up to a multiple of ``m`` (the kernels' tile sizes)."""
    return -(-n // m) * m


def interpret_mode(impl: str) -> bool:
    """``interpret=`` flag of a kernel wrapper's non-"ref" ``impl``:
    "pallas" lowers through Mosaic, "pallas_interpret" runs the same kernel
    body on the Pallas interpreter. Any other name is an error."""
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown kernel impl {impl!r}; expected 'ref', "
                         "'pallas' or 'pallas_interpret'")
    return impl == "pallas_interpret"
