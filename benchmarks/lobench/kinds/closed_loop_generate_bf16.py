"""Traffic kind ``closed_loop_generate_bf16``: the closed loop of
:mod:`lobench.kinds.closed_loop_generate`, run as it stands (its
shapes, plans, clients, gate, window, reduction and comparison), for a
configuration held in bfloat16.  The one thing that differs is the
weights job: that kind's makes every leaf in float32 in ONE jitted call
(19.4 GB at ``kimi-k2.6``: more than the chip holds); here it is
:mod:`lobench.weights_bf16`'s, leaf by leaf, no float32 copy anywhere,
as the block kind's is.  A program without the configuration's class
fails at once, before a server is booted."""

from __future__ import annotations

from lobench import rest, weights_bf16
from lobench.kinds import closed_loop_generate as base


class _Bf16Rest:
    """``lobench.rest`` with the bfloat16 weights job."""

    def __getattr__(self, name):
        return getattr(rest, name)

    @staticmethod
    def submit_weights(ctx, name, config_path, seed, as_):
        if as_ != "estimator":
            raise ValueError(as_)
        return weights_bf16.submit(ctx, name, config_path, seed)


def run(run) -> dict:
    from learningorchestra_tpu.toolkit import registry

    config = run.config
    try:
        registry.resolve(config["module_path"], config["class"])
    except Exception as exc:  # noqa: BLE001 — a program without the model
        raise SystemExit(
            f"this program cannot run {config['name']}: {exc}"
        ) from None
    # ``base.run`` finds its weights job as ``rest.submit_weights``:
    # for the length of this call that name is the bfloat16 one's.
    base.rest = _Bf16Rest()
    try:
        return base.run(run)
    finally:
        base.rest = rest
