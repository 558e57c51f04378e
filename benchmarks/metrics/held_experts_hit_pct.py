"""Model step (``ops/moe.py`` ``RoutedExperts`` with a held share): of
the experts held here, the share a step's rows reached: ``experts_hit``
of the ``lo:decode.step`` annotations (distinct held experts a layer,
summed over layers; cumulative in ``stats()["expertsHit"]``) over the
traced steps x routed layers x experts held.  The deployment, whose
experts see 32 chips' rows, reads all of them every step."""

from lobench import counts_mla, latent_turns


def read(record, run):
    turns = latent_turns.read(run)
    if not turns:
        return None
    return 100.0 * turns["experts_hit"] / (
        turns["read"] * counts_mla.routed_layers(run.cp)
        * counts_mla.held_experts(run.cp)
    )
