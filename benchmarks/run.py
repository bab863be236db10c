# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
import sys


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    print("name,us_per_call,derived")
    from . import (table1_fft_variants, table2_ablation, table3_fft2d,
                   table4_plan_autotune)
    for mod in (table1_fft_variants, table2_ablation, table3_fft2d,
                table4_plan_autotune):
        try:
            mod.run()
        except Exception as ex:                          # pragma: no cover
            print(f"{mod.__name__},0.0,ERROR={ex!r}", file=sys.stderr)
            raise


if __name__ == '__main__':
    main()
