"""positions_per_token (count): positions the block-diffusion passes fed
(the ``positions`` counts of the program's ``gen/decode`` regions) over
the tokens streamed (the ``emitted`` counts of its ``gen/step``
regions), in the traced window.  Under a static schedule of 4 passes a
block of 4 with the clean block folded into the next block's first
pass, a block costs 8 + 4 + 4 + 4 positions: 5.0 a token.  A schedule
that decides more than its share a pass, or a pass that stops feeding
what it recomputes, reads less."""
from benchmark import program_spans


def read(r):
    fed = program_spans.count_sum(program_spans.named(r, "gen/decode"),
                                  "positions")
    streamed = program_spans.count_sum(program_spans.named(r, "gen/step"),
                                       "emitted")
    if not fed or not streamed:
        return None
    return fed / streamed
