"""The simulator's mechanisms: DRAM cache, FAM controller, SPP, WFQ, the
IPC model and the event loop that ties them together (``famsim``).

Import the submodules directly (``from repro.core.famsim import
build_sim``); the package itself imports nothing, so
``repro.kernels.famsim_step`` can depend on ``repro.core.dram_cache``
while ``repro.core.famsim`` depends on the kernel package.
"""
