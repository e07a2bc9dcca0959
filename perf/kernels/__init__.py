"""Operations and bytes per call of each kernel, one file per kernel,
computed from the call's shapes. ``cost(shapes)`` returns
``(operations, bytes, peak)`` where ``peak`` names the peaks-table entry
the operations count against, or None where the cell makes no such call.
``TRACE_NAMES`` are the patterns that find the kernel's device events,
which carry the compiled program's instruction names: the TPU compiler
names a Pallas kernel's instruction after the jitted function that holds
its call (``batched_quantize.1`` inside the codec's program).
Float matmuls at ``Precision.HIGHEST`` count against the bf16 peak: their
passes run on the bf16 MXU."""
