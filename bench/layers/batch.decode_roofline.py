"""Share of the roofline reached by the decode programs in the traced
window: the least time the chip needs for the decode work done, over the
device time of the programs named ``decode_step``.

The work done is counted by the benchmark, not read from the program:
every token decoded in the window (its projections and its attention
over the tokens before it) and the bytes it needs: the KV of its own
sequence, read once, and the weights, read once per decode call.  So the
figure holds whether one call decodes one sequence or many."""
from bench.common import flops
from bench.common.peaks import least_time_s


def read(r):
    if not r.trace or not r.counts.get("decoded_tokens"):
        return None
    calls = r.trace.modules(r"\bjit_decode_step\b|\bdecode_step\(")
    if not calls:
        return None
    device_s = sum(d for _, _, d in calls) / 1e9
    nbytes = (len(calls) * flops.decoder_weight_bytes(r.config)
              + r.counts["decode_kv_bytes"])
    least, bound = least_time_s(r.counts["decode_flops"], nbytes, r.peak)
    return 100.0 * least / device_s, (
        f"{bound}-bound; {len(calls)} calls, {r.counts['decoded_tokens']} "
        f"tokens, {device_s:.6f} s on the device")
