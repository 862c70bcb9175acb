"""What is the same for every cell: finding a cell's files by name (`spec`),
the timing loop and the compile log (`loop`), the table of peaks (`peaks`),
reading a compiled program's text (`hlo`), reducing a profiler trace
(`xplane`), running a cell (`runner`) and writing the last line (`report`)."""
