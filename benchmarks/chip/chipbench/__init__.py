"""The chip benchmark's yardstick: cell specs, data and traffic generation,
load drivers, trace reduction, roofline arithmetic and the correctness
comparison.  Nothing here is imported by the program under test."""
