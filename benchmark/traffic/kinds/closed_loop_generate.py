"""Traffic kind `closed_loop_generate`: `callers` callers through
`Client.generate`, each sending its next request as the last one ends."""

from benchmark import serving


def run(cell: dict, ctx) -> dict:
    return serving.run(cell, ctx)
