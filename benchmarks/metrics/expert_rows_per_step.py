"""Model step (``ops/moe.py`` ``RoutedExperts`` with a held share):
rows a held expert sees a step: the (token, choice) pairs that reached
a held expert, by the step program's own count (``expert_rows`` of the
``lo:decode.step`` annotations; cumulative in ``stats()["expertRows"]``),
over the traced steps x routed layers x experts held.  How near the
deployment's load this chip's is: 64 slots x 8 choices / 384 experts =
1.33 here, 42.7 where an expert sees 32 chips' rows."""

from lobench import counts_mla, latent_turns


def read(record, run):
    turns = latent_turns.read(run)
    if not turns:
        return None
    return turns["expert_rows"] / (
        turns["read"] * counts_mla.routed_layers(run.cp)
        * counts_mla.held_experts(run.cp)
    )
