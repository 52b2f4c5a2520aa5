"""Presets for the paper's graph-accelerator simulations: each
accelerator model's default :class:`~repro.core.accelerators.base.AccelConfig`
at the scaled interval sizes (:mod:`repro.configs.graphsim`)."""
