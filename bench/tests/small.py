"""CPU-sized stand-ins of the cells: the same code paths at a size a test
run can hold (the sizes of the program's ``reduced_vit()`` and
``reduced(mamba2-780m)``)."""
import copy

from bench.harness import spec

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


def cell(name: str, *, closed: bool = False) -> dict:
    """(sizes, traffic) of a cell, cut to a CPU size. ``closed`` turns an
    open-loop mix into a closed loop that keeps two full batches in flight."""
    info = spec.cell(BM, name)
    sizes, traffic = copy.deepcopy(info["sizes"]), copy.deepcopy(info["traffic"])
    engine = sizes["engine"]
    engine.update(chunk=4, batch_buckets=[1, 2, 4], max_batch=4)
    if info["model"].KIND == "image":
        sizes.update(image_size=32, patch_size=4, num_classes=10, num_layers=2, d_model=64,
                     num_heads=4, d_ff=128)
        engine["seq_buckets"] = [64]
    else:
        sizes.update(num_layers=2, d_model=64, vocab_size=512, ssm_state=16, ssm_head_dim=16,
                     ssm_chunk=16)
        engine["seq_buckets"] = [32]
        traffic.update(min_len=9, max_len=32)
    if traffic.get("adaptive"):
        traffic.update(m=4, m_max=16)
    elif "m" in traffic:
        traffic["m"] = 8
    if "n_masks" in traffic:
        traffic["n_masks"] = 16
    if traffic["arrivals"] == "open":
        traffic["rate_per_s"] = 6.0
    if closed:
        traffic.update(arrivals="closed", outstanding=8, max_rate_per_s=40.0)
    return {"sizes": sizes, "traffic": traffic}
