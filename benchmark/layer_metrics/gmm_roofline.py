"""kernels: the grouped expert matmul (`gmm`, the megablox Pallas kernel that
`attention_impl="auto"` takes on a TPU) against its roofline, from the device
trace by the kernel's name: for every call shape in the traced update, the
least time the chip could take (harness/ops_bytes_moe: the larger of
operations over the bf16 peak and bytes over the HBM bandwidth; decode's
calls are bound by bytes, scoring's and the update's by operations) times
its calls, over the kernel's measured self time, in %. Nothing where the
trace has no such kernel."""

from harness import ops_bytes_moe


def read(run):
    moe = run.get("moe_trace")
    if not moe or not moe.get("kernel"):
        return None
    least = sum(c["events"] * ops_bytes_moe.grouped_matmul_floor_s(
        run["config"], run["peaks"], m=c["m"], k=c["k"], n=c["n"])
        for c in moe["kernel"])
    spent = sum(c["seconds"] for c in moe["kernel"])
    return 100.0 * least / spent if spent else None
