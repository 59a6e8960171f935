"""CPU-sized stand-ins of the cells: the same code paths at a size a test
run can hold. Each configuration cuts its own sizes (``small`` in
``bench/configs/<config>.py``); here the traffic is cut to fit them."""
import copy

from bench.harness import spec

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


def cell(name: str, *, closed: bool = False, control: bool = False) -> dict:
    """(sizes, traffic) of a cell, cut to a CPU size. ``closed`` turns an
    open-loop mix into a closed loop that keeps two full batches in flight;
    ``control`` takes the configuration's size for the control test."""
    info = spec.cell(BM, name)
    sizes = copy.deepcopy(info["model"].small(info["sizes"], control=control))
    traffic = copy.deepcopy(info["traffic"])
    if "max_len" in traffic:  # prompts from a quarter of the longest bucket up to it
        longest = max(sizes["engine"]["seq_buckets"])
        traffic.update(min_len=longest // 4 + 1, max_len=longest)
    if traffic.get("adaptive"):
        traffic.update(m=4, m_max=16)
    elif "m" in traffic:
        traffic["m"] = 8
    if "n_masks" in traffic:
        traffic["n_masks"] = 16
    if traffic["arrivals"] == "open":
        traffic["rate_per_s"] = 6.0
    if closed:
        traffic.update(arrivals="closed", outstanding=2 * sizes["engine"]["max_batch"],
                       max_rate_per_s=40.0)
    return {"sizes": sizes, "traffic": traffic}
