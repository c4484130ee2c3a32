"""The whole round's share of the chips' bf16 peak: work.py's operations
per round times the rounds completed, over the window's seconds and
the chips' peak. The count is from shapes, so this bounds any kernel's
gain."""
UNIT = "%"
LAYER = "round program (core/quantum/federated.py)"
MOVES = "rounds_per_s"


def read(win):
    if not (win.peaks and win.ops_per_round and win.rounds):
        return None
    peak = win.chips * float(win.peaks["bf16_flops_per_s"])
    return 100.0 * win.ops_per_round * win.rounds / win.seconds / peak
