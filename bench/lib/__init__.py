"""The benchmark's own code: corpus and traffic generation, trace reduction,
kernel work functions, the table of peaks, and the reference comparison.
Nothing here imports the program; ``cells`` drives it."""
