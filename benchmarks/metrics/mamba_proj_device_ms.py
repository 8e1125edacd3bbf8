"""Model: device time a traced step of the Mamba-2 mixer's two projections (the
in-projection with its bias and per-segment scale vector, and the out-projection; scope
``mamba_proj`` inside ``mamba``, ``ops/mamba2.mamba2_mixer``), forward, recomputation and
backward. With ``ssd_scan_roofline``'s time under ``mamba_ssd`` it splits
``mamba_device_ms`` into projections, scan and the rest (conv, gated norm, gates)."""

from benchmarks.harness import spans


def read(run: dict):
    red = spans.of(run)
    if red is None or "mamba_proj" not in red["label_s"]:
        return None  # no device trace, or a program whose mixer lays no such scope
    return spans.scope_ms(run, "mamba_proj")
