"""Operations and bytes the algorithm needs, one file per kernel or step.

Counts follow from shapes and from the batch's actual positions, segments
and [SUM] flags, never from how a kernel is built, so a roofline share
reads the same work whatever implements it. Element sizes are those of the
configuration's compute type (bfloat16: 2 bytes; float32 statistics: 4).
"""
