"""The grouped matmul kernel (``moe_gmm``) against its roofline for a
model that holds a part of its experts: as ``moe_gmm_roofline.doc``
(whose event reader it uses), the sum over the traced part's kernel
events of the least time the chip could take for each over the sum of
their measured times. But an event's operand is padded to EVERY
assignment (tokens x top_k rows) while the kernel's grid visits the rows
of held experts only, so the rows are not taken from the HLO line:

- a decode step's call (operand rows of at most slots x top_k): the mean
  ``held_assignments`` of the ``engine.readback`` spans (per step and
  expert layer), and their mean ``experts_touched`` held experts read;
- a prefill's call: the operand's rows times the measured held share
  (sum of ``held_assignments`` over sum of ``assignments``), and the
  family's expected count of held experts touched for its tokens.

FLOPs and bytes are the family's (``gmm_flops``, ``gmm_bytes``), of held
rows. None where the trace holds no such event or the read-backs carry
no held counts (every expert held, a dense model, a parent commit)."""
import statistics
import sys

from benchmark import manifest, model_math, span_reduce

NAME = "moe_gmm_roofline.reason"


def roofline_pct(events, fam, m: dict, slots: int, held_rows: float,
                 held_share: float, touched: float, peak):
    """``events`` [(operand rows, columns, seconds)] -> percent."""
    least = measured = 0.0
    decode_rows = -(-slots * m["top_k"] // 16) * 16
    for rows, n, seconds in events:
        k = m["d_model"] if n == m["d_ff"] else m["d_ff"]
        if rows <= decode_rows:
            work, read = held_rows, touched
        else:
            work = rows * held_share
            read = fam.experts_touched(m, rows / m["top_k"])
        least += model_math.roofline_seconds(
            fam.gmm_flops(work, k, n), fam.gmm_bytes(work, k, n, read),
            peak)[0]
        measured += seconds
    print(f"benchmark: {NAME}: {len(events)} moe_gmm events, "
          f"{measured:.4f} s measured, least {least:.4f} s; a decode call "
          f"multiplies {held_rows:.1f} rows over {touched:.1f} experts, "
          f"held share {held_share:.3f}", file=sys.stderr, flush=True)
    return 100.0 * least / measured if measured else None


def read(facts):
    events = facts.get("moe_gmm_events")
    if events is None:
        events = manifest.load_python(
            "layer_metrics", "moe_gmm_roofline.doc",
            manifest.HERE).kernel_events(facts.get("log_dir"))
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.readback")
           if {"assignments", "held_assignments", "experts_touched"}
           <= ev[3].keys()]
    span_reduce._say(NAME, len(evs), "engine.readback with held counts")
    total = sum(a["assignments"] for a in evs)
    if not events or not total:
        return None
    fam, m = manifest.model(facts["model"])
    held = [a["held_assignments"] for a in evs]
    return roofline_pct(
        events, fam, m, facts["engine"]["slots"], statistics.mean(held),
        sum(held) / total,
        statistics.mean(a["experts_touched"] for a in evs),
        model_math.peaks(facts["device"]["kind"]))
