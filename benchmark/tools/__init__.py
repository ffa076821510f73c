"""Tools a builder runs by hand on the chip: the knee sweep and the
readings that the limits of ``correct`` are set from.  No run of the
benchmark calls them."""
