"""Backend dispatch for Pallas kernels.

Compiled Pallas requires a TPU. Off-TPU the call sites take their
pure-jnp/XLA reference paths (interpreted Pallas is orders of magnitude
slower than XLA on CPU); kernel tests opt into interpreter mode with
ELASTICDL_TPU_FORCE_INTERPRET=1 so the exact same kernel code is what
they verify (tests/test_attention.py, tests/test_ops.py fixtures).

Env knobs:
  ELASTICDL_TPU_DISABLE_PALLAS=1  force the pure-jnp reference paths
  ELASTICDL_TPU_FORCE_INTERPRET=1 run the kernels in interpreter mode
                                  (opts non-TPU backends INTO the kernel
                                  path; on TPU, debugs the kernel without
                                  Mosaic)
  EDL_DISABLE_PAGED_KERNEL=1      keep paged decode on the lax.scan
                                  oracle even where the fused Pallas
                                  kernel would engage (A/B + bisection
                                  knob; the scan is the parity fallback)
"""

import os

import jax


def use_pallas():
    """Whether call sites should route through the Pallas kernels at all.

    On non-TPU backends the kernels could only run interpreted — orders
    of magnitude slower than the pure-jnp/XLA reference paths — so
    CPU runs take the reference paths and kernel tests opt in via
    FORCE_INTERPRET=1.
    """
    if os.environ.get("ELASTICDL_TPU_DISABLE_PALLAS", "") == "1":
        return False
    if os.environ.get("ELASTICDL_TPU_FORCE_INTERPRET", "") == "1":
        return True
    return is_tpu_backend()


def use_paged_kernel():
    """Whether paged_decode_attention should try the fused Pallas
    kernel (ops/attention.py::_paged_decode_fused) instead of the
    lax.scan oracle. Rides use_pallas() — same TPU/FORCE_INTERPRET/
    DISABLE_PALLAS ladder as every other kernel — with its own kill
    switch so the scan fallback stays one env var away during
    bring-up/bisection (the kernel is numerically tile-parallel where
    the scan is sequential; EDL_DISABLE_PAGED_KERNEL=1 pins the
    oracle). Shape support is the call site's problem
    (_paged_kernel_supported): this is only the policy bit."""
    if os.environ.get("EDL_DISABLE_PAGED_KERNEL", "") == "1":
        return False
    return use_pallas()


def use_cond_mask():
    """Opt-in (EDL_FLASH_COND_MASK=1): branch the flash kernels'
    per-element causal/window mask out of interior (fully-visible)
    blocks via lax.cond — an A/B candidate; default stays the
    straight-line select until a chip run proves the branch wins."""
    return os.environ.get("EDL_FLASH_COND_MASK", "") == "1"


def interpret_mode():
    """interpret= flag for pallas_call: compiled only on a real TPU."""
    if os.environ.get("ELASTICDL_TPU_FORCE_INTERPRET", "") == "1":
        return True
    return not is_tpu_backend()


def is_tpu_backend():
    """True when the default backend is TPU hardware. Any other backend
    name — known or not — is not a TPU: an unrecognized platform must
    never be handed a Mosaic kernel."""
    return jax.default_backend() == "tpu"
