"""Roofline share of the virtual-batch reassembly kernel: the bytes it must
move in the traced steps (``counts.vb_scatter_bytes_per_step`` for each
chip's rows) over the HBM peak, divided by the kernel's device time per
chip, percent.  The kernel only moves bytes, so bytes bound it."""
from bench.lib.counts import vb_scatter_bytes_per_step

# the kernel has no name of its own: its forward scatter and backward gather
# are the TPU custom calls that the custom vjp names jvp / transpose_jvp
KERNELS = {"vb_scatter": (r"^(transpose_)?jvp_\w*(\.\d+)? tpu_custom_call$",
                          "jit_step")}


def read(run):
    t, h = run["traffic"], run["host"]
    k = run["trace"]["kernels"].get("vb_scatter")
    if t["driver"] != "train" or t.get("reassembly") != "pallas" or not k \
            or not k["seconds"]:
        return None
    rows = t["global_batch"] // t["mesh"][0]
    need = vb_scatter_bytes_per_step(run["config"], rows, t["seq"])
    return 100.0 * need * h["steps"] / run["peak"]["hbm_bytes_per_s"] \
        / k["seconds"]
