"""Model FLOPs of everything the window processed (the driver's
``model_flops``, from shapes, recomputation not counted) over the
window's seconds and the chips' published peak."""


def read(args, run):
    f = run["facts"]
    if run["peaks"] is None or not f.get("model_flops"):
        return None
    peak = run["peaks"]["tflops_bf16"] * 1e12 * run["cell"]["chips"]
    return 100.0 * f["model_flops"] / f["measured_s"] / peak
