"""Model: device time a traced step of the LatentMoE's two projections (hidden -> latent
before the routed experts, latent -> hidden after the combine; scope ``moe_latent_proj``
inside ``moe``), forward, recomputation and backward."""

from benchmarks.harness import spans


def read(run: dict):
    red = spans.of(run)
    if red is None or "moe_latent_proj" not in red["label_s"]:
        return None  # no device trace, or a program without the scope
    return spans.scope_ms(run, "moe_latent_proj")
