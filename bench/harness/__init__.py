"""The benchmark's own machinery: what a cell is made of, how its data
and traffic are generated, the reference it is checked against, the
peaks and counts its rooflines use, and the reduction of a profiler
trace to device time.

Nothing here imports the program under test (``repro``): only the loop
kinds under ``bench/loops`` drive it.
"""
