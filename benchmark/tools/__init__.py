"""Tools a builder runs by hand on the chip; no benchmark run calls them."""
