"""Plain float32 references, written from the published descriptions.

No kernels, no cache, no batching tricks, nothing imported from the
program.  A configuration's ``family`` key names its module here.  Every
matmul goes through :func:`common.mm`, so that the control (the reference
computed one precision lower) is the same code with one argument changed."""
